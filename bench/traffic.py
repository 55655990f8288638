"""The one traffic generator: it reads a mix's parameters (a JSON file
under ``bench/traffic/``) and turns them, with the run's seed, into each
client's requests.

The loop is closed: each client sends its next request when the previous
one's result is back.  A mix file holds:

* ``clients``: how many; ``tenants``: the tenant name of each client
  (default ``c0``, ``c1``, ...);
* ``max_requests``: the service's ``step(max_requests=...)``;
* ``apps``: each ``{"app", "params", "fresh"}``; a round is one request of
  each app, in order.  ``params`` are fixed; ``fresh`` names parameters
  that take a value drawn from the seed that no other request of the run
  takes;
* ``rounds``: how many distinct rounds each client has.  Set-up runs
  them all (the warm-up, at the window's own shapes); the window replays
  them in the same order, over again, and a pass through them that
  starts inside the window runs whole, so every window holds whole
  passes.  The service's stored results are dropped before each round,
  so that no answer comes from its cache.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

FRESH_MAX = 1 << 31


@dataclass(frozen=True)
class Request:
    tenant: str
    app: str
    params: dict


def rounds(mix: dict, seed: int) -> list[list[list[Request]]]:
    """Each client's rounds: ``out[client][round]`` is one request of each
    app, in the mix's order.  A function of ``(mix, seed)`` alone."""
    n_clients, apps = int(mix["clients"]), mix["apps"]
    tenants = mix.get("tenants") or [f"c{i}" for i in range(n_clients)]
    n_rounds = int(mix["rounds"])
    n_fresh = n_clients * n_rounds * sum(len(a.get("fresh", ())) for a in apps)
    fresh = iter(np.random.default_rng([seed, 0x7eaf]).choice(FRESH_MAX, n_fresh, replace=False))
    out = []
    for c in range(n_clients):
        mine = []
        for _ in range(n_rounds):
            one = []
            for a in apps:
                params = dict(a.get("params", {}))
                for name in a.get("fresh", ()):
                    params[name] = int(next(fresh))
                one.append(Request(tenant=tenants[c], app=a["app"], params=params))
            mine.append(one)
        out.append(mine)
    return out


def per_round(mix: dict) -> int:
    return len(mix["apps"])
