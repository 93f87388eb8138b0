"""The one traffic generator: reads a mix file from ``bench/traffic/``.

A mix is data alone (see the files there):

* ``kind``: ``closed`` (``clients`` clients, each resubmitting when its
  result arrives) or ``open`` (arrivals on a schedule at ``rate_per_s``,
  whatever the system does);
* ``ingest``: ``submit`` (ready shards) or ``stream`` (``open``/``feed``/
  ``close``);
* ``mix``: generator names from :mod:`bench.datasets` with integer
  ``weight`` and optional keyword arguments;
* ``bank_size``, ``points_per_node`` and, for streamed ingest,
  ``feed_batch``;
* ``bank_seed``: the seed of the bank's points, and ``gaps_seed``: that
  of the open loop's inter-arrival gaps.

Every seed gets the same work: one bank of instances and one set of
gaps.  The run's seed draws the order of the gaps and the order in which
an open loop's sessions take the bank's instances; in a closed loop each
client cycles through its own block of the bank, and the seed draws the
order in which the clients start (``bench/entries/service.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import List, Tuple

import numpy as np

from bench import datasets

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Bank:
    """``instances[i]`` is a list of k ``(X, y)`` float32/int32 shards."""
    instances: List[List[Tuple[np.ndarray, np.ndarray]]]


def composition(traffic: dict) -> List[dict]:
    """The bank's entries in a fixed order: the mix repeated by weight."""
    pattern = []
    for entry in traffic["mix"]:
        pattern += [entry] * int(entry.get("weight", 1))
    return [pattern[i % len(pattern)] for i in range(traffic["bank_size"])]


def make_bank(traffic: dict, k: int, d: int) -> Bank:
    """The bank of distinct instances every run draws its sessions from."""
    insts = []
    for i, entry in enumerate(composition(traffic)):
        kw = {key: v for key, v in entry.items()
              if key not in ("gen", "weight")}
        insts.append(datasets.make(entry["gen"], traffic["points_per_node"],
                                   k, d, (traffic["bank_seed"], i), **kw))
    return Bank(insts)


def order(n: int, seed: int, salt: int) -> np.ndarray:
    """A permutation of ``range(n)`` drawn from the seed."""
    return np.random.default_rng((seed, salt)).permutation(n)


def arrivals(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop:
    ``round(rate_per_s * seconds)`` arrivals whose exponential gaps are one
    fixed set, scaled to fill ``seconds`` exactly, in an order drawn from
    the seed.  Every seed offers the same count and the same gaps."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    gaps = np.random.default_rng(traffic["gaps_seed"]).exponential(
        1.0, size=n)
    gaps = gaps[order(n, seed, 1)] * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
