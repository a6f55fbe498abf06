"""Share of the engine's slots that held a request, from ``stats()``
sampled between steps of the traced window."""


def read(run):
    samples = run["counters"].get("step_samples")
    if not samples:
        return None
    return 100.0 * sum(s["busy_slots"] / s["slots"] for s in samples) / len(samples)
