"""The one traffic generator: a mix file of parameters -> requests.

A traffic mix (``bench/traffic/<mix>.json``) states distributions; a
cell (``bench/cells/<cell>.json``) states the load: ``rate`` (requests/s,
open loop) or ``clients`` (closed loop), and ``lead_in_s``.  Every seed
gets the same work in another order, with other token ids:

- open loop: arrivals follow one fixed period of ``levels`` requests,
  repeated without a break from the lead-in through the drain.  The
  period holds every quantile level of each length distribution once,
  the sampled share, and gaps at the quantiles of the exponential that
  sum to ``levels / rate`` seconds, all in an order fixed by the mix's
  ``pattern_seed`` and not by the run's seed.  The run's seed chooses
  the entry of the period on which the window opens (and the token
  ids).  A window of whole periods (``rate x seconds`` a multiple of
  ``levels``) thus holds each entry of the period equally often in
  steady state, whichever entry it opens on: every seed meets the same
  queue;
Sizes come from a fixed table of ``levels`` quantiles of each length
distribution.  The served program compiles host-side operations per
exact prompt length, so a bounded set of lengths is also what lets set-up
warm every shape the window will use (see ``warm_lengths``).

Mix keys:

- ``loop``: ``"open"`` (arrivals on a schedule) or ``"closed"`` (each
  client sends its next request when the previous one finished);
- ``levels``: size of each quantile table, and of the open loop's period;
- ``pattern_seed``: (open loop) fixes the order of the period, 0 by
  default;
- ``prompt`` / ``output``: ``{"dist": "uniform"|"lognormal", "min",
  "max", "median", "sigma"}``; with shared prefixes ``prompt`` is the
  part after the prefix;
- ``sampled_share`` and ``sampling`` (``temperature``, ``top_p``,
  ``top_k``): that share of requests is sampled, the rest greedy;
- ``shared_prefixes``: ``{"count", "length", "zipf_s"}`` - every prompt
  opens with one of ``count`` prefixes of ``length`` tokens, chosen with
  Zipf weights ``1 / rank**zipf_s``;
- ``first_budget_fraction``: ``[lo, hi]`` (closed loop) - each client's
  first request keeps a fraction of its output budget, spread evenly over
  ``[lo, hi]``, so completions are staggered from the start.  Ranked by
  that fraction, the first requests are greedy and sampled in turn (at
  the mix's share), so the earliest to finish always hold greedy ones.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional, Tuple

import numpy as np


@dataclass
class Request:
    idx: int
    prompt: np.ndarray          # int32 token ids, shared prefix included
    max_new: int
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    sample_seed: int = 0
    prefix: int = -1            # shared-prefix id, -1 for none
    due: Optional[float] = None  # seconds from the window's opening

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


@dataclass
class Traffic:
    loop: str
    requests: List[Request]     # in submission order
    clients: int = 0            # closed loop
    prefixes: Optional[List[np.ndarray]] = None


def quantile(dist: dict, q: float) -> int:
    """The ``q`` quantile of a length distribution, clipped to its range."""
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "uniform":
        v = lo + q * (hi - lo)
    elif dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] *
                                      NormalDist().inv_cdf(q))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(v), lo), hi))


def level_table(dist: dict, levels: int) -> List[int]:
    """``levels`` evenly spaced quantiles, at (i + 1/2) / levels."""
    return [quantile(dist, (i + 0.5) / levels) for i in range(levels)]


def _even(values: List, n: int) -> List:
    """``n`` entries of the table ``values`` taken evenly: each entry
    ``n / len(values)`` times, give or take one, spread over the table."""
    k = len(values)
    return [values[(2 * i + 1) * k // (2 * n)] for i in range(n)]


def _spread(values: List, n: int, rng: np.random.Generator,
            block: int = 0) -> List:
    """``n`` values from the table, each block of ``block`` (all ``n`` by
    default) the same multiset for every seed, in an order the seed
    chooses within the block."""
    block = block or n
    out: List = []
    for start in range(0, n, block):
        m = min(block, n - start)
        part = _even(values, m)
        out += [part[i] for i in rng.permutation(m)]
    return out


def _open_stream(mix: dict, cell: dict, seconds: float,
                 rng: np.random.Generator) -> Tuple[List[int], List[int],
                                                     List[bool], List[float]]:
    """Prompt lengths, output budgets, sampled flags and due times of the
    open loop's arrivals from ``-lead_in_s`` to ``seconds + drain_s``.

    Entry ``e`` of the fixed period arrives at ``offset[e] + m x period``;
    the window opens on the entry ``rng`` draws, at time 0.  A due time
    within a nanosecond of a whole microsecond is put on it, so that the
    arrival a whole number of periods after the opening falls on the
    window's close, outside it, and not a rounding error before it."""
    k = int(mix["levels"])
    order = np.random.default_rng(int(mix.get("pattern_seed", 0)))
    prompts = [level_table(mix["prompt"], k)[i] for i in order.permutation(k)]
    outs = [level_table(mix["output"], k)[i] for i in order.permutation(k)]
    flags = _even(_share_table(float(mix.get("sampled_share", 0.0))), k)
    flags = [flags[i] for i in order.permutation(k)]
    gaps = np.array([-math.log(1.0 - (i + 0.5) / k) for i in range(k)])
    period = k / float(cell["rate"])
    gaps = gaps[order.permutation(k)] * (period / gaps.sum())
    offset = np.cumsum(gaps) - gaps
    first = int(rng.integers(k))
    lead, drain = float(cell["lead_in_s"]), float(cell.get("drain_s", 30.0))
    lo, hi = -lead, float(seconds) + drain
    out: Tuple[List, List, List, List] = ([], [], [], [])
    i = -k * (int(lead / period) + 1)
    while True:
        e, m = (first + i) % k, (first + i) // k
        t = float(offset[e] - offset[first] + m * period)
        if abs(t - round(t, 6)) < 1e-9:
            t = round(t, 6)
        i += 1
        if t >= hi:
            return out
        if t >= lo:
            for col, v in zip(out, (prompts[e], outs[e], flags[e], t)):
                col.append(v)


def _zipf_counts(count: int, s: float, n: int) -> List[int]:
    w = np.array([1.0 / (r + 1) ** s for r in range(count)])
    raw = w / w.sum() * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out))[:n - out.sum()]:
        out[i] += 1
    return out.tolist()


def warm_lengths(traffic: Traffic) -> List[int]:
    """Every prompt length (after any shared prefix) the run's requests
    hold: the same set for every seed."""
    plen = len(traffic.prefixes[0]) if traffic.prefixes else 0
    return sorted({len(r.prompt) - (plen if r.prefix >= 0 else 0)
                   for r in traffic.requests})


def request_count(mix: dict, cell: dict) -> int:
    """Requests a closed loop's schedule holds: its clients' supply."""
    return int(cell["clients"]) * int(cell.get("requests_per_client", 8))


def _share_table(share: float, size: int = 1000) -> List[bool]:
    """A table of flags, ``share`` of them set, for ``_spread``."""
    on = int(round(share * size))
    return [False] * (size - on) + [True] * on


def build(mix: dict, cell: dict, vocab: int, seed: int,
          seconds: float) -> Traffic:
    """The run's requests, made from ``seed`` alone."""
    rng = np.random.default_rng(seed)
    closed = mix["loop"] == "closed"
    clients = int(cell.get("clients", 0)) if closed else 0
    due: List[Optional[float]]
    if closed:
        n = request_count(mix, cell)
        k = int(mix["levels"])
        share = float(mix.get("sampled_share", 0.0))
        prompt_lens = _spread(level_table(mix["prompt"], k), n, rng, clients)
        out_lens = _spread(level_table(mix["output"], k), n, rng, clients)
        sampled = _spread(_share_table(share), n, rng, clients)
        due = [None] * n
    else:
        prompt_lens, out_lens, sampled, due = _open_stream(mix, cell,
                                                           seconds, rng)
        n = len(prompt_lens)
    samp = mix.get("sampling", {})
    prefixes = None
    prefix_of = [-1] * n
    sp = mix.get("shared_prefixes")
    if sp:
        prefixes = [rng.integers(0, vocab, int(sp["length"]), dtype=np.int32)
                    for _ in range(int(sp["count"]))]
        ids = [i for i, c in enumerate(_zipf_counts(int(sp["count"]),
                                                    float(sp["zipf_s"]), n))
               for _ in range(c)]
        prefix_of = [ids[i] for i in rng.permutation(n)]
    fb = mix.get("first_budget_fraction")
    if closed and fb:
        lo, hi = fb
        fracs = [lo + (hi - lo) * (i + 0.5) / clients
                 for i in range(clients)]
        order = rng.permutation(clients)
        fracs = [fracs[i] for i in order]
        # ranked by the fraction kept, the first requests are greedy and
        # sampled in turn (rank r is sampled where r * share crosses a
        # whole number), the shortest greedy
        sampled[:clients] = [math.floor((r + 1) * share) >
                             math.floor(r * share) for r in order]
    reqs: List[Request] = []
    for i in range(n):
        body = rng.integers(0, vocab, prompt_lens[i], dtype=np.int32)
        if prefix_of[i] >= 0:
            body = np.concatenate([prefixes[prefix_of[i]], body])
        budget = out_lens[i]
        if closed and fb and i < clients:
            budget = max(1, int(round(budget * fracs[i])))
        r = Request(idx=i, prompt=body, max_new=budget, prefix=prefix_of[i],
                    due=due[i], sample_seed=int(rng.integers(0, 2**31 - 1)))
        if sampled[i]:
            r.temperature = float(samp.get("temperature", 1.0))
            r.top_p = float(samp.get("top_p", 1.0))
            r.top_k = int(samp.get("top_k", 0))
        reqs.append(r)
    return Traffic(loop=mix["loop"], requests=reqs, clients=clients,
                   prefixes=prefixes)
