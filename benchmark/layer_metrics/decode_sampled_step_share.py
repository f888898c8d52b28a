"""Share of the decode launches that carried a running sampling row and so
took the sampling tail's long branch: the program's counter
``serving.decode.sampled_steps`` over ``serving.decode_steps``, in %.  The
serving kinds hand the readers no counters of the window, so this reads the
process's own since its start: warm-up, window and drain together.  A
program without the counter (any commit before PR 29) gives nothing."""

from paddle_tpu.profiler import counters

SAMPLED, STEPS = "serving.decode.sampled_steps", "serving.decode_steps"


def read(name, obs, cell, cfg, peak):
    now = counters.snapshot()
    if SAMPLED not in now or not now.get(STEPS):
        return None
    return 100.0 * now[SAMPLED] / now[STEPS]
