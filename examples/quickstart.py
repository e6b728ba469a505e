"""Quickstart: wrap a model in the Nimble engine and see the AoT speedup.

    PYTHONPATH=src python examples/quickstart.py

Mirrors the paper's user story — ``model = Nimble(model)`` and everything
else is automatic: task-graph capture, stream assignment (Algorithm 1),
memory reservation, and sealing into one replayable executable.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EagerInterpreter, Nimble


# A branchy model — parallel feature extractors joined by a sum, the
# structure where Nimble's multi-stream scheduling shines (paper Table 1).
def model(params, x):
    h = jnp.tanh(x @ params["stem"])
    branches = [jnp.tanh(h @ params[f"b{i}"]) for i in range(8)]
    out = branches[0]
    for b in branches[1:]:
        out = out + b
    return out @ params["head"]


def example_inputs(seed=0, width=128):
    """Seeded float32 ``(params, x)`` for :func:`model`: x is (32, width)."""
    rng = np.random.default_rng(seed)
    params = {"stem": rng.standard_normal((width, width), dtype=np.float32) * 0.05,
              "head": rng.standard_normal((width, 16), dtype=np.float32) * 0.05}
    for i in range(8):
        params[f"b{i}"] = rng.standard_normal((width, width), dtype=np.float32) * 0.05
    x = rng.standard_normal((32, width), dtype=np.float32)
    return params, x


def main():
    params, x = example_inputs()

    # --- engines -----------------------------------------------------------
    eager = EagerInterpreter(model, params, x)          # run-time scheduling
    nimble = Nimble(model, params, x)                   # AoT schedule, sealed
    nimble_ms = Nimble(model, params, x, pack_streams=True)  # + multi-stream

    st = nimble_ms.stats
    print(f"task graph: {st.num_tasks} tasks | "
          f"degree of concurrency {st.degree_of_concurrency} | "
          f"{st.num_streams} streams | {st.num_syncs} syncs "
          f"(= |E'| - |M|, Theorem 3)")
    print(f"reserved arena: {st.arena_bytes/1024:.0f} KiB "
          f"(reuse x{st.arena_reuse_factor:.1f})")

    ref = eager.run(params, x)
    np.testing.assert_allclose(np.asarray(nimble(params, x)), np.asarray(ref), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(nimble_ms(params, x)), np.asarray(ref), rtol=1e-4, atol=1e-5)
    print("numerics: eager == AoT == AoT+multi-stream")

    def bench(f, n=50):
        f(params, x)
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(params, x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e6

    t_e = bench(eager.run, 10)
    t_a = bench(nimble)
    t_m = bench(nimble_ms)
    print(f"eager (run-time scheduling): {t_e:9.1f} us/call")
    print(f"Nimble AoT  (single-stream): {t_a:9.1f} us/call  ({t_e/t_a:.1f}x)")
    print(f"Nimble AoT  (multi-stream) : {t_m:9.1f} us/call  ({t_e/t_m:.1f}x)")


if __name__ == "__main__":
    main()
