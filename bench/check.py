"""The comparison that decides ``correct``: served query logits against
the plain reference (``bench/models/<model>.py``), float32 at HIGHEST.

After the window a sample of the window's turns, drawn from the seed and
always holding the turn with the longest query, is replayed through the
reference: each sampled session's whole history and window turns up to
the sampled one, from a fresh state, with weights made again from the
seed.  At every position of a sampled query the program's served token
is its greedy one (the argmax of its logits); the number compared is the
widest gap by which that token's reference logit lies below the
reference's best (``greedy_gap``, in logits).

The control puts the same reference, computed on float8 operands, in the
program's place and reads the same gap for the token it puts first.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample_turns(plan, k: int, seed: int) -> List[int]:
    """k window turns drawn from the seed, plus the longest query."""
    n = len(plan.turns)
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    pick = set(rng.choice(n, size=min(k, n), replace=False).tolist())
    pick.add(max(range(n), key=lambda i: plan.turns[i].query.size))
    return sorted(pick)


def _events(plan, picks: List[int]) -> Dict[str, list]:
    """Per sampled session, its events up to its last sampled query; a
    sampled query is tagged with its turn index."""
    last = {}
    for i in picks:
        last[plan.turns[i].sid] = max(last.get(plan.turns[i].sid, -1), i)
    picked = set(picks)
    out = {s: [(op, t, None) for op, t in plan.history[s]] for s in last}
    for t in plan.turns:
        if t.sid in last and t.index <= last[t.sid]:
            out[t.sid].append(("ingest", t.chunk, None))
            out[t.sid].append(("query", t.query,
                               t.index if t.index in picked else None))
    return out


def reference_gaps(ref, m, seed: int, plan, picks: List[int],
                   served: Dict[int, np.ndarray], cache_len: int,
                   pad_ingest: int, pad_query: int,
                   control: bool = False) -> Dict[int, np.ndarray]:
    """Greedy gaps (per position) of each sampled turn.  ``served`` maps a
    turn index to the program's logits (n, vocab); with ``control`` the
    float8 reference takes the program's place and ``served`` is
    ignored."""
    import jax
    import jax.numpy as jnp
    w = ref.init_weights(m, seed)

    @jax.jit
    def gap(lg, idx):
        return jnp.max(lg, -1) - jnp.take_along_axis(lg, idx[:, None],
                                                     -1)[:, 0]

    def padded(toks, width):
        buf = np.zeros(width, np.int32)
        buf[:toks.size] = toks
        return jnp.asarray(buf)

    out = {}
    for sid, evs in _events(plan, picks).items():
        st = ref.empty_state(m, cache_len)
        sc = ref.empty_state(m, cache_len) if control else None
        for op, toks, tag in evs:
            n = int(toks.size)
            if op == "ingest":
                st = ref.ingest(m, w, st, padded(toks, pad_ingest), n)
                if control:
                    sc = ref.ingest(m, w, sc, padded(toks, pad_ingest), n,
                                    fp8=True)
                continue
            lg, st = ref.query(m, w, st, padded(toks, pad_query), n)
            if control:
                lc, sc = ref.query(m, w, sc, padded(toks, pad_query), n,
                                   fp8=True)
            if tag is None:
                continue
            if control:
                idx = jnp.argmax(lc[:n], -1)
            else:
                got = np.asarray(served[tag], np.float32)
                idx = jnp.asarray(np.argmax(got, -1).astype(np.int32))
            out[tag] = np.asarray(gap(lg[:n], idx))
    return out
