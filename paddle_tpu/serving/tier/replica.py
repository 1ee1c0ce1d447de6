"""Canonical decode replica process for the serving tier: a seeded tiny
causal LM behind a ``DecodeScheduler`` + ``ServingServer``, launched as

    python -m paddle_tpu.serving.tier.replica --port 0 --seed 1234

Why it exists: router failover and rolling-restart drills need REAL replica
processes with IDENTICAL weights — the tier's bitwise-parity contract is
"any replica answers any request with the same bytes", which only holds if
every process builds the same parameters. :func:`build_tiny_lm` pins that:
it reseeds the global key generator before construction, so every process
(and every in-process replica in tests/bench) draws the same init stream.

On start the replica prints ONE JSON line to stdout —
``{"ready": true, "port": N, "pid": P, "replica_id": ...}`` — then serves
until killed (the failover test kill -9s exactly this process). Warmup runs
BEFORE the ready line by default so the router's cold-replica gate sees a
warm replica immediately; ``--lazy-warmup`` serves first and warms in a
background thread (how the warmup-gating test produces a cold-but-alive
replica).

Knobs consumed here (strict parse, tier/knobs.py): ``PADDLE_TPU_PREFIX_CACHE``
(via DecodeEngine), ``PADDLE_TPU_DISAGG`` (build a prefill-role engine +
LocalPrefillWorker beside the decode engine), and the speculative-decoding
set ``PADDLE_TPU_SPEC_DECODE`` / ``PADDLE_TPU_SPEC_K`` (via DecodeEngine) +
``PADDLE_TPU_SPEC_DRAFTER`` (via DecodeScheduler) — also exposed as
``--spec-decode`` / ``--spec-k`` / ``--drafter`` CLI flags.

Observability flows through the environment the launcher hands this
process: ``PADDLE_TPU_TRACE_DIR`` makes the replica stream span records
(named by its replica_id via the ServingServer process label) and
``PADDLE_TPU_SLO`` adds the /healthz slo block — the ready line echoes
``trace_dir`` so drills can assert the wiring took.
"""
from __future__ import annotations

import json
import sys
import threading

__all__ = ['build_tiny_lm', 'build_replica_stack', 'main']

DEFAULT_SEED = 1234


def build_tiny_lm(seed=DEFAULT_SEED):
    """A ``TransformerLM(CausalLMConfig.tiny())`` with process-independent
    weights: the global key generator is reseeded first, so any two
    processes (or two sequential builds in ONE process) get bitwise-equal
    parameters."""
    from ...core.random import default_generator
    from ...models.causal_lm import CausalLMConfig, TransformerLM
    default_generator.seed(int(seed))
    model = TransformerLM(CausalLMConfig.tiny())
    model.eval()
    return model


def build_replica_stack(model=None, seed=DEFAULT_SEED, slots=2, block_size=4,
                        max_blocks=128, max_prompt_len=16,
                        max_new_tokens_cap=16, prompt_buckets=None,
                        prefix_cache=None, disagg=None, queue_depth=64,
                        replica_id=None, model_lock=None, spec_decode=None,
                        spec_k=None, drafter=None, kv_dtype=None,
                        denoising_steps=None):
    """(engine, scheduler, prefill_worker|None) — the replica's serving
    stack minus the HTTP listener. ``prefix_cache``/``disagg`` default to
    their env knobs, ``kv_dtype`` (handed to the engines) to its.
    ``denoising_steps`` is a window model's alone (block diffusion): the
    replica's default denoising forwards a block, which a request may
    override (docs/SERVING.md "Window models"). Used by the CLI below and, in-process, by
    tests/framework/test_serving_tier.py and benchmark/runners/
    (in-process multi-replica setups pass ONE shared ``model_lock`` so
    concurrent scheduler workers serialize their model calls)."""
    from ..decode import DecodeEngine, DecodeScheduler
    from .knobs import ENV_DISAGG, parse_flag_env
    if model is None:
        model = build_tiny_lm(seed)
    if disagg is None:
        disagg = parse_flag_env(ENV_DISAGG, default=False)
    if model_lock is None and disagg:
        model_lock = threading.RLock()
    engine = DecodeEngine(model, slots=slots, block_size=block_size,
                          max_blocks=max_blocks,
                          max_prompt_len=max_prompt_len,
                          max_new_tokens_cap=max_new_tokens_cap,
                          prompt_buckets=prompt_buckets,
                          prefix_cache=prefix_cache, model_lock=model_lock,
                          spec_decode=spec_decode, spec_k=spec_k,
                          kv_dtype=kv_dtype)
    worker = None
    if disagg:
        from .disagg import LocalPrefillWorker, PrefillReplica
        # prefill-role engine: same model + weights, its OWN scratch pool;
        # the shared lock serializes the two engines' model calls (the
        # dygraph no-grad flag is process-global)
        prefill_engine = DecodeEngine(
            model, slots=1, block_size=block_size, max_blocks=max_blocks,
            max_prompt_len=max_prompt_len,
            max_new_tokens_cap=max_new_tokens_cap,
            prompt_buckets=prompt_buckets, prefix_cache=False,
            model_lock=model_lock, kv_dtype=kv_dtype)
        worker = LocalPrefillWorker([PrefillReplica(prefill_engine)])
    scheduler = DecodeScheduler(engine, queue_depth=queue_depth,
                                replica_id=replica_id, disagg=worker,
                                drafter=drafter,
                                denoising_steps=denoising_steps)
    return engine, scheduler, worker


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description='paddle_tpu serving-tier decode replica (seeded tiny LM)')
    ap.add_argument('--host', default='127.0.0.1')
    ap.add_argument('--port', type=int, default=0)
    ap.add_argument('--seed', type=int, default=DEFAULT_SEED)
    ap.add_argument('--slots', type=int, default=2)
    ap.add_argument('--block-size', type=int, default=4)
    ap.add_argument('--max-blocks', type=int, default=128)
    ap.add_argument('--max-prompt-len', type=int, default=16)
    ap.add_argument('--max-new-tokens-cap', type=int, default=16)
    ap.add_argument('--replica-id', default=None)
    ap.add_argument('--spec-decode', type=int, choices=(0, 1), default=None,
                    help='speculative decoding on/off (default: the '
                         'PADDLE_TPU_SPEC_DECODE knob, off; env 0 always '
                         'wins — the escape hatch)')
    ap.add_argument('--spec-k', type=int, default=None,
                    help='speculative verify window (default: '
                         'PADDLE_TPU_SPEC_K, 4)')
    ap.add_argument('--drafter', default=None,
                    choices=('ngram', 'draft_model', 'off'),
                    help='draft proposer (default: PADDLE_TPU_SPEC_DRAFTER, '
                         'ngram)')
    ap.add_argument('--lazy-warmup', action='store_true',
                    help='serve immediately and warm in the background '
                         '(replica starts COLD: the router must not route '
                         'to it until /healthz warmup.done flips)')
    args = ap.parse_args(argv)

    from ...dygraph import guard
    from ..server import ServingServer
    with guard():
        engine, scheduler, worker = build_replica_stack(
            seed=args.seed, slots=args.slots, block_size=args.block_size,
            max_blocks=args.max_blocks, max_prompt_len=args.max_prompt_len,
            max_new_tokens_cap=args.max_new_tokens_cap,
            replica_id=args.replica_id,
            spec_decode=(None if args.spec_decode is None
                         else bool(args.spec_decode)),
            spec_k=args.spec_k, drafter=args.drafter)
        srv = ServingServer(None, host=args.host, port=args.port,
                            generator=scheduler)
        if args.lazy_warmup:
            threading.Thread(target=engine.warmup, daemon=True,
                             name='paddle-tpu-replica-warmup').start()
        else:
            engine.warmup()
        import os
        # the launcher (router test / bench / operator script) parses this
        # single stdout line to learn the bound port and pid
        from ...observability.trace_context import ENV_TRACE_DIR
        print(json.dumps({'ready': True, 'port': srv.port,  # lint: allow-print (launcher handshake)
                          'pid': os.getpid(),
                          'replica_id': scheduler.replica_id,
                          'trace_dir': os.environ.get(ENV_TRACE_DIR)}),
              flush=True)
        try:
            srv.serve_forever()
        finally:
            if worker is not None:
                worker.close()


if __name__ == '__main__':
    main()
