"""Share of the decode launches that uploaded at least one per-slot operand
(something other than the decode program wrote a slot since the launch
before: an admission, a last prefill chunk, a finish, a spill, a
migration): the program's counter ``serving.decode.upload_steps`` over
``serving.decode_steps``, in %.  Every other launch took all its per-slot
operands from the device.  The serving kinds hand the readers no counters
of the window, so this reads the process's own since its start: warm-up,
window and drain together.  A program without the counter (any commit
before PR 31) gives nothing."""

from paddle_tpu.profiler import counters

UPLOADS, STEPS = "serving.decode.upload_steps", "serving.decode_steps"


def read(name, obs, cell, cfg, peak):
    now = counters.snapshot()
    if UPLOADS not in now or not now.get(STEPS):
        return None
    return 100.0 * now[UPLOADS] / now[STEPS]
