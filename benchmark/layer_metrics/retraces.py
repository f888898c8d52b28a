"""Programs traced again inside the window (expected 0): ``jit.traces`` for
a training cell, ``serving.retraces`` for a serving one."""


def read(name, obs, cell, cfg, peak):
    if name == "retraces.train":
        return float(obs["counters"].get("jit.traces", 0))
    if name == "retraces.serve":
        return float(obs["retraces"])
    return None
