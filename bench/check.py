"""Decide ``correct``: the served tokens against the plain reference.

Once the window has closed and the engine's state is freed, a sample of
the requests finished in the window, drawn from the seed and always
holding the longest, is run through the configuration's plain reference
(its architecture module's ``logits_at``) in float32 over each prompt and
its served tokens.  At every served position the gap by which the served
token's reference logit lies below the reference's best is read; the
widest gap is compared with the configuration's limit.
The served path decodes greedily, so a sound run reads gaps only where
bfloat16 rounding flips a near tie.

The control puts the reference in the program's place at float8
(``mode="fp8"``, see :mod:`bench.reference`): at the same positions it
reads the gap of the token that float8 puts first.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

SAMPLE_SALT = 0x5A3C_9E11      # the sample's stream, apart from the traffic's


def sample(done: list, seed: int, target_tokens: int, max_requests: int) -> list:
    """Finished requests (``bench.serve.Sent``) to compare: the longest and,
    whatever the target, the first request in a seeded order of each lane
    the longest did not come from, so that a fault in one replica of
    several cannot pass unread; then others in that order until
    ``target_tokens`` served tokens or ``max_requests`` requests.  A
    one-lane sample is the longest, then the seeded order."""
    if not done:
        return []
    size = lambda s: len(s.req.prompt) + len(s.req.generated)
    longest = max(done, key=size)
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng((seed ^ SAMPLE_SALT) % 2**64).permutation(len(rest))
    seen, ahead = {longest.lane}, set()
    for i in order:
        if rest[i].lane not in seen:
            seen.add(rest[i].lane)
            ahead.add(i)
    picks = [longest] + [rest[i] for i in order if i in ahead]
    tokens = sum(len(s.req.generated) for s in picks)
    for i in order:
        if tokens >= target_tokens or len(picks) >= max_requests:
            break
        if i not in ahead:
            picks.append(rest[i])
            tokens += len(rest[i].req.generated)
    return picks


def positions(picks: list, max_len: int) -> tuple:
    """The token matrix (K, max_len) of prompts and served tokens, and for
    every served token the (row, column) whose logits predict it and the
    token itself."""
    K = len(picks)
    toks = np.zeros((K, max_len), np.int32)
    rows, cols, served = [], [], []
    for k, s in enumerate(picks):
        p, g = np.asarray(s.req.prompt), np.asarray(s.req.generated, np.int32)
        seq = np.concatenate([p, g[:-1]])
        toks[k, : len(seq)] = seq
        rows += [k] * len(g)
        cols += list(range(len(p) - 1, len(p) - 1 + len(g)))
        served += list(g)
    return toks, np.asarray(rows), np.asarray(cols), np.asarray(served, np.int32)


def _gaps(logits, chosen) -> np.ndarray:
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, jnp.asarray(chosen)[:, None], axis=-1)[:, 0]
    return np.asarray(best - at)


def widest_gap(arch, params: dict, model: dict, picks: list, max_len: int,
               control: bool = False) -> dict:
    """The widest gap under the float32 reference of ``arch`` (the
    configuration's architecture module) of the served tokens or, with
    ``control``, of the tokens that its float8 control puts first at the
    same positions; and what was compared."""
    toks, rows, cols, served = positions(picks, max_len)
    logits = arch.logits_at(params, model, toks, rows, cols)
    if control:
        low = arch.logits_at(params, model, toks, rows, cols, mode="fp8")
        served = np.asarray(jnp.argmax(low, axis=-1))
    gaps = _gaps(logits, served)
    return {"gap_max": float(gaps.max()), "tokens": int(len(served)),
            "requests": len(picks), "gap_nonzero": int((gaps > 0).sum())}


def complete(picks: list, vocab: int) -> list:
    """What is wrong with the sampled requests' shape: each got exactly
    the tokens it asked for, every token inside the vocabulary."""
    faults = []
    for s in picks:
        g = s.req.generated
        if len(g) != s.req.max_new_tokens or s.req.truncated:
            faults.append(f"request {s.req.rid}: {len(g)} of {s.req.max_new_tokens} tokens")
        if any(not 0 <= t < vocab for t in g):
            faults.append(f"request {s.req.rid}: token outside the vocabulary")
    return faults
