"""Whether what the timed path served is right: the comparison that
decides ``correct``.

After the window, a sample of finished requests, drawn from the seed and
always holding the one with the most served tokens, goes through the
plain reference once each: prompt plus served tokens, teacher-forced.
Sampling was keyed Gumbel-max at temperature 1: the served token is the
first maximum of ``logits + noise``, with the noise a function of the
engine's seed, the request id and the step alone.  So the reference
adds the same noise to its own float32 logits, and the number compared
is the widest gap by which a served token's score lies below the
reference's best score at that position.  A greedy request is the case
of zero noise.

The control (``mode="fp8"``) puts the reference in the program's place
at float8: at each of the same positions it picks its own best token,
and those picks take the served tokens' place in the same comparison,
so a run of the control is judged as a run of the program would be.
The program's own gap on the same requests is kept as ``program_gap``.
"""
from __future__ import annotations

import random
from collections import defaultdict
from typing import Dict, List

import jax
import jax.numpy as jnp

import reference


def sample(requests, seed: int, k: int, chunk: int) -> List:
    """Up to ``k`` finished requests: the one with the most served tokens,
    then the rest drawn from the seed, round-robin over the admission
    paths the window took (whole or chunked prompt, with or without a
    reused prefix), so that every path is compared."""
    done = [r for r in requests if r.done is not None and r.n_out > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.n_out, len(r.prompt), -r.rid))
    rng = random.Random(seed ^ 0x5A3C)
    paths: Dict[tuple, list] = defaultdict(list)
    for r in done:
        if r is not longest:
            paths[(len(r.prompt) > chunk, r.reused > 0)].append(r)
    for v in paths.values():
        rng.shuffle(v)
    picked = [longest]
    keys = sorted(paths)
    while len(picked) < k and any(paths.values()):
        for key in keys:
            if paths[key] and len(picked) < k:
                picked.append(paths[key].pop())
    return picked


def gaps(m: dict, seed: int, served_dtype, engine_seed: int, reqs: List,
         arch, mode: str = "f32", max_len: int = 2048) -> Dict[str, float]:
    """Widest score gap of the served tokens (``served_gap``) over every
    served position of ``reqs``, against the reference of architecture
    module ``arch`` (``Cell.arch``).  With
    ``mode="fp8"`` the control's own choices stand in for the served
    tokens: ``served_gap`` is theirs, and ``program_gap`` the served
    tokens'."""
    seqs = [list(r.prompt) + list(r.out[:-1]) for r in reqs]
    want = [range(len(r.prompt) - 1, len(r.prompt) - 1 + r.n_out)
            for r in reqs]
    pad = min(2048, max_len)
    kw = dict(pad_to=pad, block=min(512, pad))
    ref = reference.logits(m, seed, served_dtype, seqs, want, arch, **kw)
    ctl = (reference.logits(m, seed, served_dtype, seqs, want, arch,
                            mode="fp8", **kw) if mode == "fp8" else None)
    V = m["vocab_size"]
    served, control, n = 0.0, 0.0, 0
    for i, r in enumerate(reqs):
        g = reference.sampling_noise(engine_seed, r.rid, r.n_out, V,
                                     served_dtype)
        score = ref[i] + g
        best = jnp.max(score, axis=-1)
        tok = jnp.asarray(r.out, jnp.int32)
        got = jnp.take_along_axis(score, tok[:, None], axis=-1)[:, 0]
        served = max(served, float(jnp.max(best - got)))
        if ctl is not None:
            pick = jnp.argmax(ctl[i] + g, axis=-1)
            alt = jnp.take_along_axis(score, pick[:, None], axis=-1)[:, 0]
            control = max(control, float(jnp.max(best - alt)))
        n += r.n_out
    if ctl is None:
        return {"served_gap": served, "tokens": float(n)}
    return {"served_gap": control, "program_gap": served, "tokens": float(n)}
