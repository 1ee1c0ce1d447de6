"""chip_smoke.py (repo root) is the on-chip proof; here its contract is held
on the CPU: the script itself refuses to run without a TPU and says what it
found, and its phase functions — the same code the chip runs at full width —
go green at tiny sizes.

Three cases are child processes (the script off the chip, the script alone
in a directory, and the train phase, whose ResNet-50 trace and compile is
the slow one). A module fixture starts them before the in-process phases
run, so they overlap; the tests that read them come last."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), '..', '..'))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

_TRAIN_CHILD = (
    "import json, chip_smoke\n"
    "out = chip_smoke.train_phase(chip_smoke.CompileCounter(), batch=4,\n"
    "                             image=32, steps=4)\n"
    "print('RESULT ' + json.dumps(out))\n")


def _start(argv, cwd, env):
    return subprocess.Popen([sys.executable] + argv, cwd=cwd, env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)


@pytest.fixture(scope='module', autouse=True)
def children(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    bare = tmp_path_factory.mktemp('bare')
    with open(os.path.join(REPO, 'chip_smoke.py')) as f:
        (bare / 'chip_smoke.py').write_text(f.read())
    bare_env = dict(env)
    bare_env.pop('PYTHONPATH', None)
    procs = {'train': _start(['-c', _TRAIN_CHILD], REPO, env),
             'off_chip': _start(['chip_smoke.py'], REPO, env),
             'bare': _start(['chip_smoke.py'], str(bare), bare_env)}
    yield procs
    for p in procs.values():
        if p.poll() is None:
            p.kill()
            p.wait(timeout=30)


@pytest.fixture(scope='module')
def counter():
    return chip_smoke.CompileCounter()


def test_serve_phase_tiny(counter):
    from paddle_tpu.models.causal_lm import CausalLMConfig
    # logit_tol: f32 on the CPU, where the paged and the dense read differ
    # by an ulp or two (ROADMAP D1); 1e-5 of the logit scale is far above it
    # 5 slots of 52 blocks: 260 table entries, more than one chunk of the
    # step's read, as the phase's own sizes have
    out = chip_smoke.serve_phase(
        counter, cfg=CausalLMConfig(
            vocab_size=128, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, intermediate_size=64,
            max_position_embeddings=256),
        slots=5, block_size=4, max_blocks=300, max_prompt_len=16,
        max_new_tokens_cap=192, prompt_lens=(5, 16, 9, 12, 3), new_tokens=4,
        logit_tol=1e-5)
    assert set(out['prefill_paths'].values()) == {'XLA gather'}
    assert out['step_context_arrays'] == 0 \
        and set(out['pool_moves'].values()) == {0}


def test_static_phase_tiny(counter):
    out = chip_smoke.static_phase(counter, batch=32, steps=12)
    assert len(out['losses']) == 12


def test_kernels_phase_tiny():
    # the paged cases as the phase has them (head_dim 128, bf16, int8, a
    # 320-wide row in 384 lanes), over 9 × 30 = 270 table entries
    out = chip_smoke.kernels_phase(
        fused_shape=(1, 2, 128, 16), paged_slots=9, block_size=4,
        pages_per_seq=30, num_blocks=300, experts=(8, 32, 16),
        expert_calls=((16, 2), (40, 3)))
    assert out['fused_attention'] == 'XLA'
    assert out['moe_experts'] == 'ragged_dot'
    assert set(out['err']['experts']) == {'16x2', '40x3'}
    assert set(out['err']['paged']) == {'4x128_f32', '12x64_bf16',
                                        '12x64_int8', '5x64_f32'}
    assert max(out['err']['paged'].values()) < 1e-5


def test_result_line_holds_exactly_the_keys_the_driver_reads():
    res = json.loads(chip_smoke.result_line(chip_smoke.device_phase()))
    assert set(res) == {'ok', 'device'} and res['ok'] is True
    assert set(res['device']) == {'platform', 'kind', 'count'}
    assert isinstance(res['device']['platform'], str)
    assert isinstance(res['device']['kind'], str)
    assert type(res['device']['count']) is int


def test_script_exits_nonzero_off_chip_and_names_the_platform(children):
    out, err = children['off_chip'].communicate(timeout=300)
    assert children['off_chip'].returncode != 0
    assert "found 'cpu'" in err, err[-2000:]
    assert out.strip() == '', 'no result may be printed off the chip'


def test_script_alone_in_a_directory_fails(children):
    """Without the program beside it the script has nothing to prove."""
    out, err = children['bare'].communicate(timeout=300)
    assert children['bare'].returncode != 0
    assert 'paddle_tpu' in err and out.strip() == ''


def test_train_phase_tiny(children):
    out, err = children['train'].communicate(timeout=600)
    assert children['train'].returncode == 0, err[-3000:]
    res = json.loads(next(ln for ln in out.splitlines()
                          if ln.startswith('RESULT ')).split(' ', 1)[1])
    assert len(res['losses']) == 4 and res['losses'][-1] < res['losses'][0]


def test_group_read_phase_tiny():
    """The grouped read's four callers, each geometry cut to a few slots of
    small tables: off the chip the op is the XLA walk itself."""
    cases = tuple(
        (name, scope, 4, heads, groups, rows, head_dim, lanes, 6 if not span
         else span // 4 + 1, span and 8, shared, 1, 40)
        for (name, scope, _, heads, groups, rows, head_dim, lanes, _, span,
             shared, _, _) in chip_smoke.GROUP_READS)
    out = chip_smoke.group_read_phase(cases=cases, block_size=4, calls=1)
    assert set(out) == {c[0] for c in chip_smoke.GROUP_READS}
    assert all(v['path'] == 'XLA walk' and v['err'] == 0.0
               for v in out.values())
