"""The one traffic generator; each mix is a data file in ``bench/traffic``.

Two kinds of mix:

* ``agent`` -- open-loop agent sessions.  Sessions arrive as a Poisson
  process; each belongs to one of a few apps, and each app has its own
  shared system prompt and tool catalog (the prefix).  Turn t's prompt is
  the prefix, then the session's history (earlier instructions, drawn
  outputs and tool results), then a new instruction.  Turn t+1 is due a
  drawn tool gap after turn t was due, whether or not turn t finished.
  The last turn asks for a longer output.  A session ends before a
  prompt plus its output would pass the context.
* ``batch`` -- an offline sweep: single-turn unshared prompts with
  lognormal lengths, kept as a backlog the server never runs out of.

Sizes, turn counts, gaps and arrival times come from the mix's own
``shape_seed``, and so does their order: every ``--seed`` sends the same
requests at the same times and differs only in their token ids (and in
the weights and the sampling it seeds).  So two seeds differ in what
they send, not in how much or when: reordering the same sessions moves
which turns fall due inside the window, and changed the tails by up to
5x from seed to seed on the chip.  Token ids are drawn from
``[1, vocab - 3]``.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    due: float                       # seconds after the window opens
    prompt: List[int]
    max_new: int
    session: int = -1
    turn: int = -1
    final: bool = False
    # filled in by the run (seconds after the window opens)
    rid: int = -1
    submit: Optional[float] = None
    first: Optional[float] = None
    done: Optional[float] = None
    out: Optional[List[int]] = None
    admit_step: Optional[int] = None
    reused: int = 0

    @property
    def n_out(self) -> int:
        return len(self.out) if self.out else 0


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab - 2, size=n).tolist()


def _span(r: random.Random, lo_hi) -> int:
    lo, hi = lo_hi
    return r.randint(int(lo), int(hi))


def agent_requests(mix: dict, seed: int, seconds: float, vocab: int,
                   max_len: int) -> List[Req]:
    """Every request of an ``agent`` mix that falls due in the window,
    in due order."""
    shape = random.Random(int(mix["shape_seed"]))
    prefix_len = [_span(shape, mix["prefix_tokens"])
                  for _ in range(int(mix["apps"]))]
    rate = float(mix["session_rate_per_s"])
    gaps, t = [], 0.0
    while True:
        g = shape.expovariate(rate)
        if t + g >= seconds:
            break
        t += g
        gaps.append(g)
    templates = []
    for _ in gaps:
        turns = _span(shape, mix["turns"])
        templates.append({
            "app": shape.randrange(len(prefix_len)),
            "growth": [_span(shape, mix["growth_tokens"]) for _ in range(turns)],
            "instr": [_span(shape, mix["instruction_tokens"])
                      for _ in range(turns)],
            "out": [_span(shape, mix["output_tokens"]) for _ in range(turns)],
            "gap": [shape.uniform(*mix["tool_gap_s"]) for _ in range(turns)],
        })

    tok = np.random.default_rng(seed)
    prefixes = [_tokens(tok, n, vocab) for n in prefix_len]
    final_out = int(mix["final_output_tokens"])
    reqs: List[Req] = []
    start = 0.0
    for s, (tpl, g) in enumerate(zip(templates, gaps)):
        start += g
        history: List[int] = []
        due = start
        n_turns = len(tpl["growth"])
        for t in range(n_turns):
            instr = _tokens(tok, tpl["instr"][t], vocab)
            last = t == n_turns - 1
            max_new = final_out if last else tpl["out"][t]
            prompt = prefixes[tpl["app"]] + history + instr
            if len(prompt) + max_new > max_len:
                break
            if due < seconds:
                reqs.append(Req(due=due, prompt=prompt, max_new=max_new,
                                session=s, turn=t, final=last))
            rest = max(0, tpl["growth"][t] - len(instr))
            history = history + instr + _tokens(tok, rest, vocab)
            due += tpl["gap"][t]
    reqs.sort(key=lambda r: r.due)
    return reqs


def batch_requests(mix: dict, seed: int, vocab: int, max_len: int
                   ) -> Iterator[Req]:
    """An endless stream of ``batch`` requests: the mix's fixed list of
    sizes, over and over, with a seed's token ids."""
    shape = random.Random(int(mix["shape_seed"]))
    lo, hi = mix["prompt_tokens"]
    median = math.sqrt(lo * hi)
    sizes = []
    for _ in range(int(mix["template_requests"])):
        p = int(round(shape.lognormvariate(math.log(median),
                                           float(mix["prompt_sigma"]))))
        p = min(max(p, int(lo)), int(hi))
        o = _span(shape, mix["output_tokens"])
        o = min(o, max_len - p)
        sizes.append((p, o))
    tok = np.random.default_rng(seed)
    while True:
        for p, o in sizes:
            yield Req(due=0.0, prompt=_tokens(tok, p, vocab), max_new=o)


def warm_shapes(mix: dict, seed: int, seconds: float, vocab: int,
                max_len: int, chunk: int) -> dict:
    """Which admission shapes a mix's window needs: the prompt lengths
    that are admitted whole (their buckets), and whether any is chunked."""
    if mix["kind"] == "agent":
        reqs = agent_requests(mix, seed, seconds, vocab, max_len)
        lengths = [len(r.prompt) for r in reqs]
    else:
        gen = batch_requests(mix, seed, vocab, max_len)
        lengths = [len(next(gen).prompt)
                   for _ in range(int(mix["template_requests"]))]
    return {"whole": sorted({n for n in lengths if n <= chunk}),
            "chunked": any(n > chunk for n in lengths)}
