"""The one traffic generator: a mix file of parameters in, requests out.

Every mix under ``benchmarks/chip/traffic/`` is read by :func:`stream`
(a backlog, without end) or :func:`build` (an open-loop schedule).  A mix
describes sessions of one or more turns:

* ``arrival``: ``{"process": "poisson", "rate_req_s": r}`` sends sessions
  open-loop so that requests (turns and retries) arrive at ``r`` per second
  on average; ``{"process": "backlog"}`` makes every request due at t = 0.
* ``sessions``: ``turns_min``/``turns_max`` (uniform), ``turn_gap_mean_s``
  (exponential gap between turns, kept whether or not the previous turn
  has finished: a replayed conversation trace), ``retry_share`` (share of
  turns that are sent again unchanged after a gap, as a user who asks to
  regenerate an answer).
* ``system_prompts``: ``count`` prompts of ``tokens`` tokens that every
  session's first turn starts with, chosen with Zipf(``zipf_s``)
  popularity; ``null`` for none.
* ``message``: a list of segments that make a turn's new user content
  (a document, then a question, ...), each a length distribution.
* ``reply``: the length of the assistant reply that a later turn carries
  in its history (seeded tokens, as in a replayed trace).
* ``output``: the number of tokens to generate.
* ``preroll_s`` (open loop): how long after the first due request the
  harness opens its window, so that sessions overlap as in steady state.
* ``warm_system_prompts``: the harness prefills every system prompt during
  set-up, as a deployment's prefix cache holds them in steady state.
* ``structure_seed``: where given, every quantity but the token ids is
  drawn from this fixed seed, so that every run replays the same trace
  (arrivals, turns, lengths, sharing) with its own token ids.

A length distribution is ``{"dist": "lognormal", "median", "sigma",
"min", "max"}`` or ``{"dist": "uniform", "min", "max"}``; lognormal draws
are cut (clipped) to ``[min, max]``, which keeps the median.

Draws are samples from numpy generators: the token ids from the seed,
uniform over the vocabulary, and everything else (arrivals, turns,
lengths, sharing) from ``structure_seed`` where the mix gives one, else
from the seed too.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as the harness sends it."""
    rid: int
    due_s: float                 # relative to the first due request
    tokens: tuple[int, ...]
    max_new_tokens: int
    session: int
    turn: int                    # 0-based turn within the session
    retry: bool                  # an unchanged re-send of the previous turn


def length(spec: dict, rng: np.random.Generator, scale: float = 1.0) -> int:
    """A length drawn from its distribution.  ``scale`` shrinks every
    length (CPU rehearsals); 1.0 leaves them as the mix states."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = int(rng.integers(lo, hi + 1))
    elif spec["dist"] == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * rng.standard_normal())
        x = min(max(round(x), lo), hi)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return max(1, round(x * scale))


def mean_turns(mix: dict) -> float:
    """Requests per session: the mean number of turns, each re-sent with
    probability ``retry_share``."""
    s = mix.get("sessions") or {}
    lo, hi = s.get("turns_min", 1), s.get("turns_max", 1)
    return (lo + hi) / 2 * (1 + s.get("retry_share", 0.0))


def _zipf_index(rng: np.random.Generator, count: int, s: float) -> int:
    w = np.arange(1, count + 1, dtype=np.float64) ** -s
    return int(rng.choice(count, p=w / w.sum()))


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(seed & (2**64 - 1)))


def _systems(sp, rng, vocab: int, scale: float) -> list[list[int]]:
    if not sp:
        return []
    n = max(1, round(sp["tokens"] * scale))
    return [rng.integers(0, vocab, n).tolist() for _ in range(sp["count"])]


def system_prompts(mix: dict, *, seed: int, vocab: int,
                   scale: float = 1.0) -> list[list[int]]:
    """The mix's shared system prompts for this seed, as :func:`stream`
    draws them."""
    return _systems(mix.get("system_prompts"), _rng(seed), vocab, scale)


def stream(mix: dict, *, seed: int, vocab: int, max_len: int,
           scale: float = 1.0):
    """Requests in the order they are generated, without end: sessions
    one after another, each session's turns (and retries) together.  The
    first session is due at 0, and so is every request of a backlog.
    ``max_len`` caps prompt plus output: a prompt is cut from its end to
    ``max_len`` minus its output length."""
    rng = _rng(seed)
    sp = mix.get("system_prompts")
    # drawn before anything else, as system_prompts() draws them
    systems = _systems(sp, rng, vocab, scale)
    shape = _rng(mix["structure_seed"]) if "structure_seed" in mix else rng
    arrival = mix["arrival"]
    sess = mix.get("sessions") or {}
    backlog = arrival["process"] == "backlog"
    if not backlog:
        if arrival["process"] != "poisson":
            raise ValueError(
                f"unknown arrival process {arrival['process']!r}")
        session_rate = arrival["rate_req_s"] / mean_turns(mix)

    def tokens(n: int) -> list[int]:
        return rng.integers(0, vocab, n).tolist()

    segs = mix["message"]

    rid = 0
    t = 0.0
    session = 0
    while True:
        if not backlog and session:
            t += shape.standard_exponential() / session_rate
        n_turns = int(shape.integers(sess.get("turns_min", 1),
                                     sess.get("turns_max", 1) + 1))
        history = (list(systems[_zipf_index(shape, len(systems),
                                            sp["zipf_s"])])
                   if systems else [])
        due = 0.0 if backlog else t
        for turn in range(n_turns):
            if turn:
                due += (0.0 if backlog else shape.standard_exponential()
                        * sess["turn_gap_mean_s"])
                history += tokens(length(mix["reply"], shape, scale))
            for seg in segs:
                history += tokens(length(seg, shape, scale))
            n_out = length(mix["output"], shape, scale)
            prompt = tuple(history[:max_len - n_out])
            yield Req(rid, due, prompt, n_out, session, turn, False)
            rid += 1
            if shape.random() < sess.get("retry_share", 0.0):
                gap = (0.0 if backlog else shape.standard_exponential()
                       * sess["turn_gap_mean_s"])
                again = due + gap
                yield Req(rid, again, prompt, n_out, session, turn, True)
                rid += 1
        session += 1


def build(mix: dict, *, seed: int, vocab: int, max_len: int,
          horizon_s: float | None = None, n_requests: int | None = None,
          scale: float = 1.0) -> list[Req]:
    """The requests of one run, sorted by due time: for an open-loop mix
    every session that starts by ``horizon_s``; for a backlog the first
    ``n_requests``."""
    gen = stream(mix, seed=seed, vocab=vocab, max_len=max_len, scale=scale)
    if mix["arrival"]["process"] == "backlog":
        if n_requests is None:
            raise ValueError("a backlog mix needs n_requests")
        return [next(gen) for _ in range(n_requests)]
    if horizon_s is None:
        raise ValueError("an open-loop mix needs horizon_s")
    out, starts = [], set()
    for r in gen:
        if r.session not in starts:
            if r.due_s > horizon_s:
                break
            starts.add(r.session)
        out.append(r)
    out.sort(key=lambda r: (r.due_s, r.rid))
    return out
