"""On-chip smoke run: the serving path at published width on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four replicas, one per chip

One chip: builds ``stablelm-1.6b`` at its published widths in bf16
(random weights from a seed), serves seeded requests through
``AsyncDispatcher`` (pool stepping) -> ``Dispatcher`` -> ``ServingEngine``
-> ``ScheduleCache``, runs the same requests on a second engine stepped
directly (same weights, own KV cache) and requires identical tokens, then
seals the paper's engine (``Nimble(..., pack_streams=True)``) and compares
it with the eager interpreter.

``--chips 4`` runs only the multi-device path: four replicas in this one
process, each with its weights made on and its engine pinned to its own
chip, behind one pool-stepping dispatcher; every replica must produce the
tokens a directly stepped one-chip engine produces.

Needs a TPU: any other backend exits non-zero before any phase runs.  The
last line of output, printed only when every check passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The timings printed are smoke timings of one cold run, not benchmarks.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as C  # noqa: E402
from quickstart import example_inputs  # noqa: E402
from quickstart import model as branchy_model  # noqa: E402
from repro.core import EagerInterpreter, Nimble  # noqa: E402
from repro.dispatch import AsyncDispatcher, ScheduleCache  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import forward, init_model  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

ARCH = "stablelm-1.6b"
SEED = 0
MAX_SLOTS = 8
MAX_LEN = 1024
BUCKETS = (64, 128, 256, 512)
N_REQUESTS = 16
PROMPT_LENS = (16, 512)
MAX_NEW = 32
RESULT_TIMEOUT_S = 600.0

# Nimble vs eager on the branchy model, both under "highest" matmul
# precision.  quickstart.py's rtol=1e-5 assumes CPU float32 dots; on a TPU
# a float32 dot at "highest" is emulated with several bf16 passes (about
# 1e-6 relative per dot), and the sealed, stream-packed program fuses and
# sums in another order than the op-by-op interpreter, so the outputs
# (magnitude ~1, through three chained dots and tanh) may differ by a few
# 1e-6 (on a TPU v5e with JAX 0.9.0 they agreed exactly).  1e-4 leaves
# room for that and still fails on any wrong schedule, which moves outputs
# by O(0.1).
CORE_RTOL = 1e-4
CORE_ATOL = 1e-4


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    """Raise :class:`SmokeFailure` with ``what`` unless ``ok``."""
    if not ok:
        raise SmokeFailure(what)


def tree_bytes(tree) -> int:
    """Bytes held by the arrays of ``tree``."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def make_prompts(vocab: int, n: int = N_REQUESTS,
                 lens: tuple = PROMPT_LENS, seed: int = SEED) -> list:
    """``n`` seeded int32 prompts with lengths drawn from ``lens`` (inclusive)."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(lens[0], lens[1] + 1, n)
    return [rng.integers(0, vocab, int(k)).astype(np.int32) for k in lengths]


def init_params(cfg, device=None, seed: int = SEED):
    """Random weights from ``seed``, made on ``device`` (JAX's default if None)."""
    with jax.default_device(device):
        params, _ = init_model(jax.random.key(seed), cfg)
    return jax.block_until_ready(params)


def build_engine(cfg, params, schedule_cache, *, device=None,
                 max_slots: int = MAX_SLOTS, max_len: int = MAX_LEN,
                 buckets: tuple = BUCKETS) -> ServingEngine:
    """A sealed engine (decode + every prefill bucket compiled, or found in
    ``schedule_cache``) with its own KV cache."""
    return ServingEngine(
        cfg, params, max_slots=max_slots, max_len=max_len, bucketing=buckets,
        schedule_cache=schedule_cache, device=device,
    )


def check_placement(name: str, engine: ServingEngine, device) -> None:
    """Every array of the engine's weights and KV cache lives on ``device``."""
    for what, tree in (("params", engine.params), ("kv cache", engine.kv_cache)):
        for leaf in jax.tree_util.tree_leaves(tree):
            check(leaf.devices() == {device},
                  f"{name}: {what} on {leaf.devices()}, expected {device}")


def check_tokens(name: str, tokens: list, vocab: int, max_new: int) -> None:
    """Every request got ``max_new`` tokens, each in ``[0, vocab)``."""
    for i, toks in enumerate(tokens):
        check(len(toks) == max_new,
              f"{name} request {i}: {len(toks)} tokens, expected {max_new}")
        check(all(0 <= t < vocab for t in toks),
              f"{name} request {i}: token outside [0, {vocab}): {toks}")


def check_logits(cfg, params, prompt) -> None:
    """A full-sequence forward over ``prompt`` gives finite logits of the
    expected shape (NaN logits would still argmax to valid tokens)."""
    logits, _ = jax.jit(forward, static_argnums=2)(
        params, {"tokens": prompt[None, :]}, cfg)
    logits = np.asarray(logits[..., : cfg.vocab], np.float32)
    check(logits.shape == (1, len(prompt), cfg.vocab),
          f"logits shape {logits.shape}")
    check(bool(np.isfinite(logits).all()), "non-finite logits")


def serve(engines: dict, prompts: list, max_new: int = MAX_NEW) -> tuple:
    """Submit every prompt to every engine through one pool-stepping
    ``AsyncDispatcher`` and wait on every future.

    Returns ``({name: [tokens per prompt]}, builds_on_thread, seconds)``;
    raises :class:`SmokeFailure` if a request finished with an error."""
    dispatcher = AsyncDispatcher(
        stepping="pool", pool_size=len(engines),
        max_pending=len(engines) * len(prompts),
    )
    for name, engine in engines.items():
        dispatcher.register_model(name, engine)
    t0 = time.perf_counter()
    with dispatcher:
        futures = {
            name: [dispatcher.submit(name, p, max_new_tokens=max_new)
                   for p in prompts]
            for name in engines
        }
        done = {name: [f.result(timeout=RESULT_TIMEOUT_S) for f in fs]
                for name, fs in futures.items()}
    seconds = time.perf_counter() - t0
    for name, reqs in done.items():
        for i, req in enumerate(reqs):
            check(req.error is None, f"{name} request {i} failed: {req.error}")
    tokens = {name: [list(r.generated) for r in reqs]
              for name, reqs in done.items()}
    return tokens, dispatcher.builds_on_thread, seconds


def run_direct(engine: ServingEngine, prompts: list,
               max_new: int = MAX_NEW) -> list:
    """The same requests stepped directly on ``engine`` until drained."""
    reqs = [Request(rid=i, prompt=p, max_new_tokens=max_new)
            for i, p in enumerate(prompts)]
    for req in reqs:
        engine.submit(req)
    engine.run_until_drained()
    for req in reqs:
        check(req.done and req.error is None,
              f"direct request {req.rid}: done={req.done} error={req.error}")
    return [list(r.generated) for r in reqs]


def core_phase(seed: int = SEED) -> float:
    """Seal ``Nimble(pack_streams=True)`` on quickstart's branchy model and
    compare it with the eager interpreter; returns the largest abs error."""
    params, x = example_inputs(seed)
    with jax.default_matmul_precision("highest"):
        eager = EagerInterpreter(branchy_model, params, x)
        nimble = Nimble(branchy_model, params, x, pack_streams=True)
        ref = np.asarray(eager.run(params, x))
        got = np.asarray(nimble(params, x))
    check(got.shape == ref.shape == (32, 16), f"core output shape {got.shape}")
    check(bool(np.isfinite(got).all()), "core output not finite")
    err = float(np.max(np.abs(got - ref)))
    check(bool(np.allclose(got, ref, rtol=CORE_RTOL, atol=CORE_ATOL)),
          f"Nimble vs eager: max abs error {err:.3g} beyond "
          f"rtol={CORE_RTOL} atol={CORE_ATOL}")
    return err


def one_chip(device, cfg, prompts: list, *, max_new: int = MAX_NEW,
             **engine_kw) -> None:
    """Serve phase, the same requests run directly, and the core phase.

    ``engine_kw`` overrides :func:`build_engine`'s sizes."""
    print(f"config {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}")
    t0 = time.perf_counter()
    params = init_params(cfg, device)
    print(f"params: {tree_bytes(params)} bytes, initialised in "
          f"{time.perf_counter() - t0:.1f}s")

    cache = ScheduleCache(capacity=32)
    t0 = time.perf_counter()
    served = build_engine(cfg, params, cache, device=device, **engine_kw)
    seal_s = time.perf_counter() - t0
    # shares the weights and (through the schedule cache) the executables
    direct = build_engine(cfg, params, cache, device=device, **engine_kw)
    print(f"kv cache: {tree_bytes(served.kv_cache)} bytes per engine "
          f"({served.max_slots} slots x {served.max_len} positions)")
    print(f"compile: decode + prefill buckets {served.prompt_buckets} sealed "
          f"in {seal_s:.1f}s")
    check_placement("served", served, device)
    check_placement("direct", direct, device)

    check_logits(cfg, params, prompts[0])
    tokens, builds, seconds = serve({cfg.name: served}, prompts, max_new)
    tokens = tokens[cfg.name]
    n_tokens = sum(len(t) for t in tokens)
    check(builds == 0, f"builds_on_thread={builds}, expected 0")
    check_tokens("served", tokens, cfg.vocab, max_new)
    lens = [len(p) for p in prompts]
    distinct = len({t for toks in tokens for t in toks})
    print(f"served {len(prompts)} requests (prompts {min(lens)}-{max(lens)} "
          f"tokens, {max_new} new each): {n_tokens} tokens ({distinct} "
          f"distinct) in {seconds:.2f}s = {n_tokens / seconds:.1f} tok/s "
          f"(smoke timing, not a benchmark); builds_on_thread={builds}")

    reference = run_direct(direct, prompts, max_new)
    check(tokens == reference,
          "served tokens differ from the directly stepped engine's")
    decode_compiles = served.stats.decode_compiles + direct.stats.decode_compiles
    check(decode_compiles == 1,
          f"decode compiled {decode_compiles} times, expected once")
    print(f"direct run: tokens identical to the served run; decode compiled "
          f"{decode_compiles}x, prefill "
          f"{served.stats.prefill_compiles + direct.stats.prefill_compiles}x")

    err = core_phase()
    print(f"core: Nimble(pack_streams=True) matches eager, max abs error {err:.3g}")


def four_chips(devices: list, cfg, prompts: list, *, max_new: int = MAX_NEW,
               **engine_kw) -> None:
    """One replica per device behind one dispatcher vs a one-chip direct run.

    ``engine_kw`` overrides :func:`build_engine`'s sizes."""
    cache = ScheduleCache(capacity=64)
    replicas = {}
    t0 = time.perf_counter()
    for i, dev in enumerate(devices):
        engine = build_engine(cfg, init_params(cfg, dev), cache, device=dev,
                              **engine_kw)
        check_placement(f"replica{i}", engine, dev)
        replicas[f"replica{i}"] = engine
    print(f"{len(replicas)} replicas of {cfg.name} built and sealed in "
          f"{time.perf_counter() - t0:.1f}s on "
          f"{[str(d) for d in devices]}")

    one_chip_engine = build_engine(cfg, replicas["replica0"].params, cache,
                                   device=devices[0], **engine_kw)
    reference = run_direct(one_chip_engine, prompts, max_new)
    check_tokens("one-chip direct", reference, cfg.vocab, max_new)

    tokens, builds, seconds = serve(replicas, prompts, max_new)
    check(builds == 0, f"builds_on_thread={builds}, expected 0")
    for (name, engine), dev in zip(replicas.items(), devices):
        check_placement(name, engine, dev)
        check(tokens[name] == reference,
              f"{name} tokens differ from the one-chip direct run")
        check(engine.stats.decode_compiles == 1,
              f"{name}: decode compiled {engine.stats.decode_compiles} times")
    n_tokens = sum(len(t) for toks in tokens.values() for t in toks)
    for dev in devices:
        stats = dev.memory_stats() or {}
        print(f"  {dev}: {stats.get('bytes_in_use', 'n/a')} bytes in use")
    print(f"served {len(prompts)} requests on each of {len(replicas)} replicas: "
          f"{n_tokens} tokens in {seconds:.2f}s = {n_tokens / seconds:.1f} "
          f"tok/s (smoke timing, not a benchmark); builds_on_thread={builds}; "
          f"every replica's tokens identical to the one-chip run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-replica path (needs four chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")
    print(f"compile cache: {use_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    cfg = C.get(ARCH)
    prompts = make_prompts(cfg.vocab)
    if args.chips == 4:
        four_chips(devices[:4], cfg, prompts)
    else:
        one_chip(devices[0], cfg, prompts)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
