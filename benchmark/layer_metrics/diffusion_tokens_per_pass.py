"""Tokens emitted over row passes run, from the window's own counter delta
(``serving.decode_tokens`` over ``serving.diffusion.row_passes``): useful
outcomes over attempts.  0.8 at 5 passes a block of 4 (4 denoising steps
and the commit), 2.0 at the least a block can cost; a first block that
opens with prompt tokens, or an output cut inside a block, emits fewer."""


def read(name, obs, cell, cfg, peak):
    moved = obs.get("counters") or {}
    passes = moved.get("serving.diffusion.row_passes")
    if not passes:
        return None
    return moved.get("serving.decode_tokens", 0) / passes
