"""Serving launcher: the long-running inference service Mirage keeps alive.

Loads the newest checkpoint if one exists (the successor sub-job resumes
the same weights), then serves a stream of synthetic requests through the
slot-based engine until the wall-clock guard fires — checkpointing engine
weights on exit for the next sub-job in the chain.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 16 [--ckpt-dir checkpoints/svc]
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--wall-limit", type=float, default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    import numpy as np
    from repro.models import registry, transformer
    from repro.serve import Request, ServeEngine
    from repro.train import PreemptionGuard
    from repro.train.checkpoint import latest_step, restore_checkpoint

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, step = restore_checkpoint(args.ckpt_dir, {"params": params})
        params = state["params"]
        print(f"[serve] restored weights from step {step}")

    guard = PreemptionGuard(args.wall_limit, grace_s=5.0,
                            install_signals=False)
    eng = ServeEngine(cfg, params, batch=args.batch, s_max=args.s_max)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
        eng.add_request(Request(rid=rid, prompt=prompt, max_new=args.max_new))
    t0 = time.time()
    served_tokens = 0
    while (eng.queue or any(r is not None for r in eng.slot_req)):
        if guard.should_stop():
            print("[serve] wall limit — checkpoint and hand off")
            break
        served_tokens += eng.step()
    dt = time.time() - t0
    print(f"[serve] {served_tokens} tokens in {dt:.1f}s "
          f"({served_tokens/max(dt,1e-9):.1f} tok/s)")


if __name__ == "__main__":
    main()
