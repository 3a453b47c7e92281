"""Decide ``correct``: what the timed path served, against the reference.

After the window a sample of the finished requests, drawn from the
seed, is run through the plain float32 reference (``reference.py``)
once each, over its prompt and the tokens it was served.  For every
served token the reference gives the gap by which that token's logit
lies below its own best; the number compared is the widest gap.
Greedy decoding in bfloat16 picks the reference's best token or one
within rounding of it; a step that computes something else picks
tokens far down.

The control puts the reference in the program's place at the next
precision down (int8, see ``reference.py``): at each position of the
same prompts and tokens it reads the gap of the token the int8
reference puts first.

Separately, every request that finished must have been served exactly
its ``max_new`` tokens, all in the vocabulary, the stream of events
equal to the request's own record (a count with the limit 0).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import reference


@jax.jit
def _gaps(logits, toks):
    at = jnp.take_along_axis(logits, toks[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=-1) - at


@jax.jit
def _first(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def bad_streams(recs, vocab: int) -> int:
    """Finished requests whose served stream is not exactly right."""
    bad = 0
    for r in recs:
        if r.done is None:
            continue
        toks = np.asarray(r.tokens)
        if (len(toks) != r.spec.max_new
                or list(r.tokens) != list(r.req.generated)
                or (len(toks) and (toks.min() < 0 or toks.max() >= vocab))):
            bad += 1
    return bad


def sample(recs, seed: int, *, tokens: int, max_requests: int) -> List:
    """Finished in-window requests drawn from the seed: the longest
    (prompt + output), one that was preempted if any was, then others
    until about ``tokens`` served tokens or ``max_requests``."""
    done = [r for r in recs if r.in_window and r.done is not None]
    if not done:
        return []
    rng = np.random.default_rng([seed % (1 << 63), 7])
    longest = max(done, key=lambda r: (len(r.spec.prompt) + r.spec.max_new,
                                       -r.spec.rid))
    picked = [longest]
    pre = [r for r in done if r.req.n_preemptions and r is not longest]
    if pre:
        picked.append(pre[rng.integers(len(pre))])
    for i in rng.permutation(len(done)):
        if (len(picked) >= max_requests
                or sum(len(r.tokens) for r in picked) >= tokens):
            break
        if all(done[i] is not p for p in picked):
            picked.append(done[i])
    return picked


def _pad(a, n):
    out = np.zeros(n, np.int32)
    out[:len(a)] = a
    return out


def compare(weights, dims, picked, *, control: Optional[str] = None
            ) -> Dict[str, float]:
    """Widest gap of the served tokens (and, with ``control``, of the
    tokens the control's reference puts first) below the reference's
    best logit, over every token of ``picked``."""
    out = {"served": 0.0, "tokens": 0}
    if control:
        out["control"] = 0.0
    for r in picked:
        served = np.asarray(r.tokens, np.int32)
        P, n = len(r.spec.prompt), len(served)
        seq = np.concatenate([r.spec.prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(P - 1, P - 1 + n)
        ref = reference.logits(weights, dims, seq, rows)
        g = np.asarray(_gaps(ref, jnp.asarray(_pad(served, ref.shape[0]))))
        out["served"] = max(out["served"], float(g[:n].max()))
        out["tokens"] += n
        if control:
            low = reference.logits(weights, dims, seq, rows, quant=control)
            c = _first(low)
            gc = np.asarray(_gaps(ref, c))
            out["control"] = max(out["control"], float(gc[:n].max()))
            del low
        del ref
    return out
