"""The general load generator: turns a traffic mix (a data file under
``bench/traffic/``) into requests and drives them through
``ServeFrontend`` in an open or a closed loop.

Traffic keys:

- ``loop``: ``"open"`` (requests due on a Poisson schedule at
  ``rate`` per second, whether or not earlier ones finished) or
  ``"closed"`` (``clients`` callers, each sending its next request when
  its last one is answered, drawing from a pool of ``pool`` requests;
  with ``pool_seed`` the pool is the same for every seed, which sets
  only its order).
- ``mix``: share of each kind, ``range_counts`` / ``range_ids`` /
  ``knn``.
- ``max_hits`` (range_ids), ``k`` and ``max_cand`` (kNN: the first
  value and then each re-send's, as the kNN contract asks of a client
  whose answer came back flagged).
- ``half_side``: range box half-side as a fraction of the home
  partition's side, drawn uniformly from the pair.
- ``tenants``: share of the requests each tenant sends.
- ``check``: how many answers of each kind the reference checks.
- ``frontend`` (optional): ``FrontendConfig`` settings, where the mix
  is served otherwise than by the frontend's defaults.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

from . import gen

KINDS = ("range_counts", "range_ids", "knn")


@dataclasses.dataclass
class Req:
    """One request and what became of it."""
    kind: str
    payload: np.ndarray
    tenant: str
    due: float = 0.0          # seconds after the window opens (open loop)
    sent: float = -1.0        # absolute perf_counter seconds
    done: float = -1.0        # final answer; -1 when none came
    value: object = None      # the final answer
    outcome: str = ""         # "ok", or what the frontend said instead
    resends: int = 0          # kNN re-sends after a flagged answer


def make_requests(rng, traffic: dict, n: int, centres: np.ndarray,
                  part_boxes: np.ndarray) -> list:
    """``n`` requests of the mix, kinds in a seeded order."""
    kinds = list(traffic["mix"])
    p = np.asarray([traffic["mix"][k] for k in kinds], np.float64)
    counts = np.floor(p / p.sum() * n).astype(int)
    counts[0] += n - counts.sum()
    order = rng.permutation(np.repeat(np.arange(len(kinds)), counts))
    who = gen.tenants(rng, traffic["tenants"], n)
    payload = {}
    for i, kind in enumerate(kinds):
        m = int(counts[i])
        if kind == "knn":
            payload[kind] = iter(gen.knn_points(rng, centres, m))
        else:
            payload[kind] = iter(gen.range_boxes(
                rng, centres, part_boxes, m, tuple(traffic["half_side"])))
    return [Req(kinds[i], next(payload[kinds[i]]), who[j])
            for j, i in enumerate(order)]


def requests_for(rng, traffic: dict, seconds: float, centres, part_boxes):
    """The run's requests: an open loop's are due on its schedule, a
    closed loop's pool is cycled by its clients."""
    if traffic["loop"] == "open":
        due = gen.arrivals(rng, traffic["rate"], seconds)
        reqs = make_requests(rng, traffic, len(due), centres, part_boxes)
        for r, t in zip(reqs, due):
            r.due = float(t)
        return reqs
    return make_requests(rng, traffic, traffic["pool"], centres, part_boxes)


async def send(fe, req: Req, traffic: dict) -> None:
    """One request through the frontend to its final answer: a flagged
    kNN answer is sent again at each further ``max_cand``."""
    tenant = req.tenant
    if req.kind == "range_counts":
        resp = await fe.range_counts(req.payload, tenant=tenant)
    elif req.kind == "range_ids":
        resp = await fe.range_ids(req.payload, traffic["max_hits"],
                                  tenant=tenant)
    else:
        for i, mc in enumerate(traffic["max_cand"]):
            resp = await fe.knn(req.payload, traffic["k"], mc,
                                tenant=tenant)
            if not resp.ok or not resp.value[2]:
                break
            if i + 1 < len(traffic["max_cand"]):
                req.resends += 1
    req.outcome = resp.outcome.value
    if resp.ok and not (req.kind == "knn" and resp.value[2]):
        req.value = resp.value
        req.done = time.perf_counter()
    elif resp.ok:
        req.outcome = "flagged"


async def open_loop(fe, reqs: list, traffic: dict, t0: float,
                    drain: float) -> float:
    """Send each request when it falls due (``t0 + due``), then wait up
    to ``drain`` seconds past the last due time for the answers.
    -> the generator's worst lateness, seconds."""
    tasks, late = [], 0.0
    for r in reqs:
        wait = t0 + r.due - time.perf_counter()
        if wait > 0:
            await asyncio.sleep(wait)
        r.sent = time.perf_counter()
        late = max(late, r.sent - (t0 + r.due))
        tasks.append(asyncio.ensure_future(send(fe, r, traffic)))
    end = t0 + (reqs[-1].due if reqs else 0.0) + drain
    await wait_all(tasks, end)
    return late


async def closed_loop(fe, pool: list, traffic: dict, t0: float,
                      seconds: float, drain: float) -> list:
    """``clients`` callers cycle through ``pool`` until the window
    closes; each waits for its answer before sending again.  -> the
    requests sent, in the order they were started."""
    started: list = []
    t_end = t0 + seconds
    nxt = iter(range(1 << 62))

    async def client():
        while time.perf_counter() < t_end:
            i = next(nxt)
            proto = pool[i % len(pool)]
            r = dataclasses.replace(proto, sent=time.perf_counter())
            started.append(r)
            await send(fe, r, traffic)

    tasks = [asyncio.ensure_future(client())
             for _ in range(traffic["clients"])]
    await wait_all(tasks, t_end + drain)
    return started


async def wait_all(tasks: list, deadline: float) -> None:
    """Wait for ``tasks`` until ``deadline`` (perf_counter seconds),
    then cancel what is left; a task's own error is raised."""
    if tasks:
        timeout = max(deadline - time.perf_counter(), 0.0)
        _, pending = await asyncio.wait(tasks, timeout=timeout)
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    for t in tasks:
        if not t.cancelled() and t.exception() is not None:
            raise t.exception()
