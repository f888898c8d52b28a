"""Host milliseconds a step of the window waited for its batch: the
program's ``io.prefetch_stall_ns`` counter (time ``DevicePrefetcher``'s
consumer blocked on an empty queue) over the window's steps.  Reads 0.0
when the feed keeps ahead."""


def read(name, obs, cell, cfg, peak):
    if not obs.get("steps") or "counters" not in obs:
        return None
    stall_ns = obs["counters"].get("io.prefetch_stall_ns", 0)
    return stall_ns / 1e6 / obs["steps"]
