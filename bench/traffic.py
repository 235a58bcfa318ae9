"""The one generator of serving traffic.  A traffic file gives the
parameters; this turns them and a seed into requests.

Every seed gets the same arrival times and the same multiset of request
sizes (system prompt, own tokens, output), drawn once from
``base_seed``; the seed assigns the sizes to the arrivals in its own
order and draws every token.  So two seeds ask the same amount of work of
the system at the same moments, and differ in which request has which
size and what it says.  (Bursty arrivals in another order per seed made
the tail of an open loop a property of the seed more than of the
system.)

Parameters (``kind`` "open" or "backlog"):

- ``requests``: how many requests to make; "auto" makes ``rate`` times
  the window's seconds, with the gaps scaled so that the last one
  arrives just before the window closes: every seed then has the same
  requests due in the window;
- ``tenants``: ``prompt_lengths`` and ``zipf`` — every request starts with
  the system prompt of one tenant, chosen with Zipf popularity; without
  it, prompts share nothing;
- ``suffix`` (with tenants) or ``prompt`` (without): ``lengths`` and
  ``weights`` of the request's own tokens;
- ``output``: lognormal ``median`` and ``sigma``, clipped to
  [``min``, ``max``];
- ``arrivals`` (open): ``rate`` per second, and ``cv`` of the Gamma gaps.
  A backlog arrives all at time 0.

Each request's own tokens start with a token that no other request or
system prompt starts with, so the prefix index matches system prompts
and nothing else.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Set

import numpy as np


@dataclasses.dataclass
class Request:
    arrival: float              # seconds after the window opens
    tokens: np.ndarray          # prompt, int32
    new_tokens: int
    tenant: int                 # -1 without tenants
    shared: int                 # tokens of the tenant's system prompt


def n_requests(params: Dict[str, Any], seconds: float) -> int:
    n = params["requests"]
    if n == "auto":
        n = int(round(params["arrivals"]["rate"] * seconds))
    return int(n)


def _categorical(rng, values, weights, n):
    w = np.asarray(weights, float)
    return rng.choice(np.asarray(values), size=n, p=w / w.sum())


def sizes(params: Dict[str, Any], n: int, seconds: float
          ) -> Dict[str, np.ndarray]:
    """The seed-independent multisets: own-token lengths, output lengths,
    tenants and gaps, each of ``n`` entries."""
    rng = np.random.default_rng(params["base_seed"])
    own = params["suffix"] if "tenants" in params else params["prompt"]
    out = {"own": _categorical(rng, own["lengths"], own["weights"], n)}
    o = params["output"]
    raw = np.exp(np.log(o["median"]) + o["sigma"] * rng.standard_normal(n))
    out["new"] = np.clip(np.round(raw), o["min"], o["max"]).astype(int)
    if "tenants" in params:
        t = params["tenants"]
        k = len(t["prompt_lengths"])
        pop = 1.0 / np.arange(1, k + 1) ** t["zipf"]
        out["tenant"] = _categorical(rng, np.arange(k), pop, n)
    else:
        out["tenant"] = np.full(n, -1)
    if params["kind"] == "open":
        a = params["arrivals"]
        shape = 1.0 / a["cv"] ** 2
        gap = rng.gamma(shape, 1.0 / (a["rate"] * shape), n)
        if params["requests"] == "auto":
            gap *= seconds * n / (n + 1) / gap.sum()
        out["gap"] = gap
    else:
        out["gap"] = np.zeros(n)
    return out


def generate(params: Dict[str, Any], seed: int, vocab: int,
             seconds: float) -> List[Request]:
    """The requests of one run, in order of arrival."""
    n = n_requests(params, seconds)
    base = sizes(params, n, seconds)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)             # which request gets which sizes
    perm = {k: v[order] for k, v in base.items() if k != "gap"}
    perm["gap"] = base["gap"]
    tenants = params.get("tenants", {}).get("prompt_lengths", [])
    # first tokens: one per system prompt and one per request, all distinct
    firsts = rng.choice(vocab, size=len(tenants) + n, replace=False)
    systems = []
    for i, length in enumerate(tenants):
        toks = rng.integers(0, vocab, length, dtype=np.int64)
        toks[0] = firsts[i]
        systems.append(toks)
    out, t = [], 0.0
    for i in range(n):
        t += float(perm["gap"][i])
        own = rng.integers(0, vocab, int(perm["own"][i]), dtype=np.int64)
        own[0] = firsts[len(tenants) + i]
        tenant = int(perm["tenant"][i])
        shared = systems[tenant] if tenant >= 0 else own[:0]
        prompt = np.concatenate([shared, own]).astype(np.int32)
        arrival = t if params["kind"] == "open" else 0.0
        out.append(Request(arrival=arrival, tokens=prompt,
                           new_tokens=int(perm["new"][i]), tenant=tenant,
                           shared=len(shared)))
    return out


def prompt_shapes(params: Dict[str, Any]) -> Dict[str, Set]:
    """Every shape a run of this traffic can ask the engine for:
    ``miss`` prompt lengths (whole prompts prefilled) and ``hit`` pairs
    (system prompt length, own length) extended from the cache."""
    if "tenants" in params:
        own = params["suffix"]["lengths"]
        shared = sorted(set(params["tenants"]["prompt_lengths"]))
        return {"miss": {s + o for s in shared for o in own},
                "hit": {(s, o) for s in shared for o in own}}
    return {"miss": set(params["prompt"]["lengths"]), "hit": set()}
