"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (dryrun.py sets XLA_FLAGS before any jax init).

Mesh semantics (DESIGN.md §5):
  single-pod: (data=16, model=16)        — 256 chips (one v5e pod)
  multi-pod:  (pod=2, data=16, model=16) — 512 chips

'data'  — batch parallel for train/prefill; doubles as the LIME pipeline
          *stage* axis in the serving engine.
'model' — tensor parallel (heads / ffn / experts / vocab).
'pod'   — batch/replica parallel across pods (bursty request replicas).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Every mesh in the repo is built here, with all axes `Auto`, on the
    first prod(shape) devices.

    The engine runs the stage axis manual inside a `shard_map` and leaves
    the rest ('model', 'pod') to GSPMD; `jax.make_mesh` defaults to
    `Explicit` axes, under which the vocab-sharded embedding gather in the
    step raises `ShardingTypeError`."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_stage: int = 4, n_model: int = 2):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh((n_stage, n_model), ("data", "model"))
