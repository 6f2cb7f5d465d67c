"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests and benches must
see the real (1-device) CPU; distributed engine tests re-exec themselves in
a subprocess with a forced device count (the `run_worker` fixture)."""
import importlib.util
import os
import pathlib
import re
import subprocess
import sys

try:
    import hypothesis                                    # noqa: F401
except ModuleNotFoundError:
    # dev extra not installed: register the deterministic stub under the
    # real name so `from hypothesis import given, ...` keeps working
    _spec = importlib.util.spec_from_file_location(
        "hypothesis",
        pathlib.Path(__file__).parent / "_hypothesis_stub.py")
    _mod = importlib.util.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    sys.modules["hypothesis"] = _mod

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(autouse=True)
def _tracer_restored():
    """A test that installs a process tracer (importing
    chipbench/program_trace.py does) leaves the next test the one it
    found: tracing stays off unless a test turns it on."""
    from repro.obs.trace import get_tracer, set_tracer
    prev = get_tracer()
    yield
    set_tracer(prev)


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def assert_finite(x, msg=""):
    assert bool(jnp.isfinite(jnp.asarray(x, jnp.float32)).all()), msg


# ----------------------------------------------------------------------------
# shared model/backend factories (hoisted from the per-file copies that
# test_specdec.py / test_engine_hetero.py / test_prefixcache.py grew)
# ----------------------------------------------------------------------------
def _tiny_dense_config(n_layers=2, **overrides):
    """The tiny dense transformer the spec/verify tests all share."""
    from repro.configs.base import Family, ModelConfig
    kw = dict(name="d", family=Family.DENSE, n_layers=n_layers, d_model=32,
              n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64, head_dim=8)
    kw.update(overrides)
    return ModelConfig(**kw)


@pytest.fixture
def tiny_dense_cfg():
    """2-layer toy ModelConfig; call the factory for other shapes."""
    return _tiny_dense_config()


@pytest.fixture
def tiny_dense_factory():
    return _tiny_dense_config


@pytest.fixture(scope="session")
def smoke_model():
    """(cfg, params) for reduced gemma3-1b — session-scoped: param init
    dominates the runtime of the serving tests that share it. Params are
    an immutable pytree, so sharing across tests is safe."""
    from repro.configs.registry import get_smoke_config
    from repro.models import model as M
    cfg = get_smoke_config("gemma3-1b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _make_sim_backend(slots, *, spec=None, prompt=64, arch="llama2-13b",
                      plan=None, **kw):
    """SimBackend over the E3 fleet: the serving tests' standard rig."""
    from repro.configs.registry import get_config
    from repro.core.cost_model import CostEnv, Workload
    from repro.core.profiles import env_E3, mbps
    from repro.serving import SimBackend
    cfg = get_config(arch)
    w = Workload(cfg, mb=1, ctx=prompt, n_micro=slots)
    return SimBackend(CostEnv(env_E3(), mbps(200), w), plan, n_slots=slots,
                      prompt_tokens=prompt, spec=spec, **kw)


@pytest.fixture
def sim_backend():
    """Factory: sim_backend(slots, spec=..., prompt=...) -> SimBackend."""
    return _make_sim_backend


# ----------------------------------------------------------------------------
# subprocess worker re-exec (the convention test_engine.py established)
# ----------------------------------------------------------------------------
def _run_worker(worker_src, *argv, devices=8, timeout=900):
    """Re-exec a worker script with src/ on PYTHONPATH and (by default) a
    forced host device count; devices=None keeps the real 1-device CPU.
    Worker output is forwarded so its per-case lines show on failure."""
    env = dict(os.environ)
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parent.parent / "src")
    r = subprocess.run([sys.executable, "-c", worker_src, *argv], env=env,
                       capture_output=True, text=True, timeout=timeout)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr[-2000:])
    return r


@pytest.fixture
def run_worker():
    return _run_worker


# ----------------------------------------------------------------------------
# compiled-program shapes: the guard against the step program copying a
# stack of layers' weights (the layer scan reads each layer in place)
# ----------------------------------------------------------------------------
_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z]\w*\[[^=]*?)\s"
    r"([a-z][\w\-]*)\(", re.M)
_HLO_ARRAY = re.compile(r"[a-z]\w*\[([\d,]*)\]")


def hlo_results(hlo_text):
    """(name, opcode, [dims of each array it yields]) of every
    instruction of a compiled program's text."""
    return [(name, opc, [tuple(int(d) for d in dims.split(",") if d)
                         for dims in _HLO_ARRAY.findall(shape)])
            for name, shape, opc in _HLO_INSTR.findall(hlo_text)]


def matrix_leaf_shapes(layer_shapes):
    """The per-layer leaf shapes of rank >= 2. A norm scale's stack, (n,
    d_model), has the shape of n activation rows, so only the matrices —
    all but a few KB of a layer — are guarded."""
    return {tuple(s) for s in layer_shapes if len(s) >= 2}


def is_layer_stack(dims, leaf_shapes):
    """`dims` is a stack of >= 2 layers of one of `leaf_shapes`."""
    return len(dims) >= 3 and dims[0] >= 2 and tuple(dims[1:]) in leaf_shapes
