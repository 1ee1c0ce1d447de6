"""Test config: force CPU backend with 8 virtual devices so mesh/distributed
tests run without TPU hardware (SURVEY §4)."""
import os
import tempfile

os.environ['JAX_PLATFORMS'] = 'cpu'

# tier-1 compiles CPU executables; keep them out of the in-checkout cache
# (core/compile_cache.DEFAULT_CACHE_DIR), which the chip tool would copy to
# the machine with the chip. jax reads this at import, and every child
# process a test starts inherits it. A fixed path: a rerun hits.
os.environ.setdefault(
    'JAX_COMPILATION_CACHE_DIR',
    os.path.join(tempfile.gettempdir(), 'paddle_tpu_tier1_xla_cache'))

# tier-1 runs with the static verifier live at every IR pass boundary, so
# every test doubles as a false-positive check on the analysis layer
# (paddle_tpu/analysis/; ISSUE 10). setdefault: a test (or CI matrix job)
# may still pin its own level, including 'off'.
os.environ.setdefault('PADDLE_TPU_VERIFY', 'passes')
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

import jax
assert jax.default_backend() == 'cpu', jax.default_backend()

import pytest


@pytest.fixture(autouse=True)
def fresh_programs():
    """Isolate each test: fresh default programs, scope, and name counter."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.scope import Scope
    import paddle_tpu.core.scope as scope_mod
    old_main = fluid.framework._main_program_
    old_start = fluid.framework._startup_program_
    old_scope = scope_mod._global_scope
    old_gen = unique_name.generator
    fluid.framework._main_program_ = fluid.Program()
    fluid.framework._startup_program_ = fluid.Program()
    scope_mod._global_scope = Scope()
    unique_name.generator = unique_name.UniqueNameGenerator()
    yield
    fluid.framework._main_program_ = old_main
    fluid.framework._startup_program_ = old_start
    scope_mod._global_scope = old_scope
    unique_name.generator = old_gen
