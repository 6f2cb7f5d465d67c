"""Mean host time of an admission step (EngineBackend.start_batch:
prefill on the replicated params, the engine's weight re-split and cache
seeding, the first token's sampling), over the window of the traced run.
start_batch returns host ints, so the step has finished on the device."""


def read(run):
    w = run.window
    v = [t1 - t0 for t0, t1, kind, _ in w.steps
         if kind == "prefill" and w.inside(t1)]
    return 1e3 * sum(v) / len(v) if v else None
