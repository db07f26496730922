"""Host time per engine tick outside device waits: the engine's own phase
clock, (admission + prefill + decode - host_sync) / ticks, over the
window (``ServingEngine.stats()["phase_time_s"]``)."""


def read(run):
    ph, ticks = run.stats["phase_time_s"], run.stats["ticks"]
    if not ticks:
        return None
    host = ph["admission"] + ph["prefill"] + ph["decode"] - ph["host_sync"]
    return 1e3 * host / ticks
