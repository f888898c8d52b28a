"""Batch/remat sweep for the GPT-760M MFU leg (perf round 5).

Reuses bench.py's measurement protocol (_run_leg) so sweep numbers stay
comparable to the tracked bench.  Results: scripts/PERF_NOTES.md.

Usage: python scripts/bench_760m.py [batch] [recompute]
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    rec = sys.argv[2] if len(sys.argv) > 2 else "selective_lean"
    if rec == "none":
        rec = False
    import jax

    from bench import _PEAKS, _run_leg
    from paddle_tpu.core import compile_cache
    from paddle_tpu.models import GPTConfig

    compile_cache.enable()
    peak = _PEAKS[jax.devices()[0].device_kind]["bf16_flops"]

    cfg = GPTConfig.gpt3_760m(vocab_size=50304, max_seq_len=1024,
                              dtype="bfloat16", use_flash_attention=True,
                              recompute=rec)
    t0 = time.perf_counter()
    tps, spread, n_params, *_ = _run_leg(cfg, batch, 1024, 10, 1)
    mfu = tps * 6 * n_params / peak
    print(f"batch={batch} rec={rec} params={n_params/1e6:.0f}M "
          f"tok/s={tps:.0f} MFU={mfu:.4f} "
          f"(total {time.perf_counter()-t0:.0f}s)")


if __name__ == "__main__":
    main()
