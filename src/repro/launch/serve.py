"""Serving launcher: batched requests through the AoT serving engine.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
        --requests 16 --max-new 12
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

import repro.configs as C
from repro.launch.compile_cache import use_compile_cache
from repro.models import init_model
from repro.serving import Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--dtype", default="float32")
    args = ap.parse_args()
    use_compile_cache()

    cfg = C.get(args.arch, smoke=args.smoke)
    cfg = dataclasses.replace(cfg, dtype=args.dtype)
    params, _ = init_model(jax.random.key(0), cfg)

    t0 = time.perf_counter()
    engine = ServingEngine(
        cfg, params, max_slots=args.slots, max_len=args.max_len,
        prompt_buckets=(16, 32),
    )
    print(f"AoT scheduling (seal prefill x{len(engine.prompt_buckets)} + decode): "
          f"{time.perf_counter() - t0:.1f}s")

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab, int(rng.integers(4, 30))).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    done = engine.run_until_drained()
    wall = time.perf_counter() - t0

    st = engine.stats
    lat = [r.t_done - r.t_submit for r in done]
    ttft = [r.t_first - r.t_submit for r in done]
    print(f"served {len(done)} requests in {wall:.2f}s | "
          f"decode steps {st.steps} | {st.decode_tok_per_s:,.0f} tok/s decode")
    print(f"TTFT p50 {np.percentile(ttft, 50)*1e3:.1f}ms p99 {np.percentile(ttft, 99)*1e3:.1f}ms | "
          f"latency p50 {np.percentile(lat, 50)*1e3:.1f}ms")


if __name__ == "__main__":
    main()
