"""Share of the decode launches that were enqueued while the launch before
them was not yet read back, so that the read-back's latency and the host's
turn between two steps ran under the device's next launch: the program's
counter ``serving.decode.overlapped_steps`` over ``serving.decode_steps``,
in %.  The other launches followed a read-back (the launch uploaded an
operand, or a row got its last token from the launch before).  The serving
kinds hand the readers no counters of the window, so this reads the
process's own since its start: warm-up, window and drain together.  A
program without the counter gives nothing."""

from paddle_tpu.profiler import counters

OVERLAPPED, STEPS = "serving.decode.overlapped_steps", "serving.decode_steps"


def read(name, obs, cell, cfg, peak):
    now = counters.snapshot()
    if OVERLAPPED not in now or not now.get(STEPS):
        return None
    return 100.0 * now[OVERLAPPED] / now[STEPS]
