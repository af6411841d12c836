"""Elastic mesh derivation + straggler watchdog scaffolding.

``derive_mesh`` builds the best (data, model[, pod]) mesh for *whatever*
device count survives a failure: model parallelism is capped by what the
architecture shards cleanly, the rest goes to data.  Checkpoints are
device-count agnostic (checkpoint/manager.py), so the recovery story is:

  node dies → job restarts on N' hosts → derive_mesh(N') → restore latest
  checkpoint → pjit reshards params/optimizer on first step → training
  continues (data pipeline is (seed, step)-pure, so no data loss/dup).

``Watchdog`` is the host-level straggler detector: heartbeat timestamps
per host, flagging hosts whose step time exceeds k·median.  On real
clusters the action is to evict + restart elastically; on this single-host
container the tests exercise detection only.
"""
from __future__ import annotations

import dataclasses
import time
from collections import defaultdict

import jax
import numpy as np

from repro.launch.mesh import make_mesh


def derive_mesh(n_devices: int | None = None, model_parallel: int = 16, multi_pod: bool = False, pod_size: int = 256):
    """Best-effort mesh for an arbitrary device count."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    n = len(devs)
    if multi_pod and n > pod_size and n % pod_size == 0:
        pods = n // pod_size
        mp = min(model_parallel, pod_size)
        return make_mesh((pods, pod_size // mp, mp), ("pod", "data", "model"), devices=devs)
    mp = model_parallel
    while mp > 1 and n % mp:
        mp //= 2
    return make_mesh((n // mp, mp), ("data", "model"), devices=devs)


@dataclasses.dataclass
class HostBeat:
    step: int
    t: float


class Watchdog:
    """Straggler detection from per-host heartbeats."""

    def __init__(self, n_hosts: int, slack: float = 3.0, min_samples: int = 3):
        self.n_hosts = n_hosts
        self.slack = slack
        self.min_samples = min_samples
        self._beats: dict[int, list[HostBeat]] = defaultdict(list)

    def beat(self, host: int, step: int, t: float | None = None):
        self._beats[host].append(HostBeat(step, time.monotonic() if t is None else t))

    def step_times(self) -> dict[int, float]:
        out = {}
        for h, beats in self._beats.items():
            if len(beats) >= 2:
                dts = [b2.t - b1.t for b1, b2 in zip(beats, beats[1:])]
                out[h] = float(np.median(dts[-8:]))
        return out

    def stragglers(self) -> list[int]:
        times = self.step_times()
        if len(times) < self.min_samples:
            return []
        med = float(np.median(list(times.values())))
        return [h for h, t in times.items() if t > self.slack * med]

    def missing(self, timeout: float, now: float | None = None) -> list[int]:
        now = time.monotonic() if now is None else now
        out = []
        for h in range(self.n_hosts):
            beats = self._beats.get(h)
            if not beats or now - beats[-1].t > timeout:
                out.append(h)
        return out
