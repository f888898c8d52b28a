"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals over the window."""


def read(name, obs, cell, cfg, peak):
    red = obs.get("trace")
    if not red or red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
