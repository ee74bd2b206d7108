"""The one traffic generator: a traffic file's parameters and a seed in,
a plan of ``create_service`` calls out.

A traffic file (``benchmark/traffic/<name>.json``) names a ``generator``
kind and its parameters; a new mix is a new file.  Nothing here imports
the program: a plan is plain data the harness replays against the control
API.

``open_loop``: deploys arrive on a schedule whether or not the system
keeps up.  The sequence of deploys (services, shapes, replicas) and of the
gaps before them is drawn once from the file's ``base_seed`` for the run's
length, so every ``--seed`` offers the same services, the same number of
tasks and the same gaps; the seed only chooses where in the cycle the
window starts (the builder's contract, for seeds that would change the
work: the same set of sizes and arrivals, in another order).

``closed_loop``: a fixed number of clients, each creating its next
service when the last is wholly ASSIGNED.
"""

from __future__ import annotations

import json
import math
import os
import random
from typing import Dict, List, NamedTuple

HERE = os.path.dirname(os.path.abspath(__file__))


class ServiceCall(NamedTuple):
    """One ``create_service`` the generator will make."""

    due_s: float        # seconds after the window opens
    name: str
    shape: str
    replicas: int
    deploy: int         # index of the stack it belongs to


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        params = json.load(f)
    if params.get("generator") not in GENERATORS:
        raise ValueError(f"traffic {name}: unknown generator "
                         f"{params.get('generator')!r}")
    return params


def _log_uniform_int(rng: random.Random, lo: int, hi: int) -> int:
    return min(hi, max(lo, int(math.exp(
        rng.uniform(math.log(lo), math.log(hi + 1))))))


def _draw_replicas(rng: random.Random, bands: List[dict]) -> int:
    u, acc = rng.random(), 0.0
    for band in bands:
        acc += band["share"]
        if u < acc:
            break
    return _log_uniform_int(rng, band["lo"], band["hi"])


def open_loop_schedule(params: dict, seconds: float,
                       seed: int) -> List[ServiceCall]:
    """Every call due in a window of ``seconds``; the last is due before
    the window closes.

    The sequence of deploys and of the gaps before them is drawn from
    ``base_seed`` alone; ``--seed`` chooses where in that cycle the
    window starts.  Dealt in a free order, the same services gave tails
    that differed by a third from seed to seed (which large service
    meets which burst); rotated, every seed meets the same bursts."""
    total = int(round(params["tasks_per_s"] * seconds))
    base = random.Random(params["base_seed"])
    lo, hi = params["services_per_deploy"]
    shapes = params["shapes"]
    deploys: List[List[int]] = []
    left = total
    while left > 0:
        stack = []
        for _ in range(base.randint(lo, hi)):
            if left <= 0:
                break
            r = min(_draw_replicas(base, params["replicas"]), left)
            stack.append(r)
            left -= r
        deploys.append(stack)
    # the shapes cycle service by service; a whole number of cycles, so
    # that the cycle also holds where the rotation joins end to start
    # (a run of fusable groups never outgrows what the warm-up drove)
    while sum(map(len, deploys)) % len(shapes):
        stack = max(deploys, key=max)
        i = stack.index(max(stack))
        stack[i:i + 1] = [stack[i] - stack[i] // 2, stack[i] // 2]
    named, n = [], 0
    for d, stack in enumerate(deploys):
        named.append([(f"d{d:04d}-s{n + j:05d}",
                       shapes[(n + j) % len(shapes)], r)
                      for j, r in enumerate(stack)])
        n += len(stack)
    # gamma gaps with the stated coefficient of variation, scaled so the
    # last deploy is due a little before the close
    cv = params["arrival"]["cv"]
    gaps = [base.gammavariate(1.0 / (cv * cv), 1.0) for _ in deploys]
    scale = seconds * params["arrivals_within"] / sum(gaps)

    turn = random.Random(seed).randrange(len(deploys))
    calls: List[ServiceCall] = []
    t = 0.0
    for k in range(len(deploys)):
        i = (turn + k) % len(deploys)
        t += gaps[i] * scale
        calls.extend(ServiceCall(t, name, shape, r, i)
                     for name, shape, r in named[i])
    return calls


def closed_loop_clients(params: dict) -> List[Dict]:
    """One entry a client: the shape and size of every service it
    creates, one after another, for as long as the window lasts."""
    return [{"client": i, "shape": c["shape"], "replicas": c["replicas"]}
            for i, c in enumerate(params["clients"])]


GENERATORS = {"open_loop": open_loop_schedule,
              "closed_loop": closed_loop_clients}


def offered_tasks(calls: List[ServiceCall]) -> int:
    return sum(c.replicas for c in calls)
