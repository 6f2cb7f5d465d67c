"""The serving launcher on the CPU: the engine is built on however many
devices exist, asking for more is an error, and the compile cache lands
where it is told (launch/serve.py, launch/compile_cache.py)."""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.launch import serve as S

SRC = pathlib.Path(__file__).parent.parent / "src"


def _serve_tokens(srv, prompts, max_new):
    reqs = [srv.queue.submit(p, max_new_tokens=max_new) for p in prompts]
    srv.serve_all()
    return [list(r.output) for r in reqs]


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_stages_1_builds_engine_matching_engineless(impl):
    """--stages 1 on one device runs the InterleavedEngine (one stage,
    streamed-layer fetch included), and its greedy tokens equal the
    engine-less single-device decode's."""
    import jax

    from repro.configs.registry import get_smoke_config
    from repro.serving import LimeServer, SamplerConfig
    args = S.parse_args(["--arch", "gemma3-1b", "--smoke", "--stages", "1",
                         "--impl", impl, "--pattern", "bursty",
                         "--max-len", "32"])
    S.resolve_stages(args, len(jax.devices()))
    cfg = get_smoke_config(args.arch)
    srv = S.build_server(cfg, args)
    eng = srv.engine
    assert eng is not None and eng.impl == impl
    assert eng.plan.n_stage == 1 and tuple(eng.plan.k_off_list) == (1,)
    ref = LimeServer(cfg, srv.params, engine=None, max_len=args.max_len,
                     pattern="sporadic", sampler=SamplerConfig())
    assert ref.slots == srv.slots == 1
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, 8) for _ in range(2)]
    got = _serve_tokens(srv, prompts, 6)
    want = _serve_tokens(ref, prompts, 6)
    assert got == want and all(len(t) == 6 for t in got)


def test_stages_default_to_device_count():
    import jax
    args = S.parse_args(["--arch", "gemma3-1b", "--smoke"])
    S.resolve_stages(args, len(jax.devices()))
    assert args.stages == len(jax.devices())


def test_more_stages_than_devices_exits_nonzero():
    """No silent engine-less fallback: the launcher refuses, naming both
    counts."""
    import jax
    n = len(jax.devices())
    with pytest.raises(SystemExit) as e:
        S.main(["--arch", "gemma3-1b", "--smoke", "--stages", str(n + 3)])
    assert e.value.code not in (0, None)
    assert f"needs {n + 3} devices; {n} exist" in str(e.value.code)


def test_pallas_with_tensor_parallel_exits_nonzero():
    args = S.parse_args(["--arch", "gemma3-1b", "--smoke", "--stages", "1",
                         "--tp", "2", "--impl", "pallas"])
    with pytest.raises(SystemExit) as e:
        S.resolve_stages(args, 8)
    assert "--tp 1" in str(e.value.code)


_CACHE_WORKER = r"""
import jax, jax.numpy as jnp
from repro.launch.compile_cache import compile_stats, enable_compile_cache
print("DIR", enable_compile_cache())
print("CONFIG", jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.tanh(x @ x.T).sum())(jnp.ones((64, 64))).block_until_ready()
print("WRITES", compile_stats()["writes"])
"""


def _cache_worker(env_dir):
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", _CACHE_WORKER], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_compile_cache_follows_env_var(tmp_path):
    out = _cache_worker(tmp_path)
    assert out["DIR"] == out["CONFIG"] == str(tmp_path)
    assert int(out["WRITES"]) >= 1 and any(tmp_path.iterdir())


def test_compile_cache_default_is_fixed_in_checkout():
    from repro.launch.compile_cache import DEFAULT_DIR
    assert DEFAULT_DIR == SRC.parent.resolve() / ".jax_cache"
    # the default is configured without compiling (nothing lands in the
    # checkout from the test): import + enable only
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c",
         "import jax; from repro.launch.compile_cache import "
         "enable_compile_cache as e; print(e()); "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(DEFAULT_DIR)] * 2
