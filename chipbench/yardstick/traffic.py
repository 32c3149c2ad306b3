"""Traffic generation: seeded data and an open-loop release schedule.

The data generators are copies kept with the benchmark, so that a change
to the program cannot change the traffic it is measured on. The release
schedule runs on its own thread and never waits for the system: every unit
of work carries its due time, and the thread records how late it released
each one.
"""
from __future__ import annotations

import threading
import time
from typing import Iterator, Optional

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream `stream` of a run's seed (any size)."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), int(stream)])


def program_seed(seed: int) -> int:
    """The run's seed folded to the int32 the system's counter RNG takes."""
    s = int(seed) & 0xFFFFFFFF
    return s - 2 ** 32 if s >= 2 ** 31 else s


# Copied from src/repro/data/streams.py (flow_size_chunks).
def flow_size_chunks(
    num_groups: int,
    num_chunks: int,
    chunk_t: int,
    rng: Optional[np.random.Generator] = None,
    mu_range=(5.5, 9.0),
    sigma_range=(0.8, 1.4),
) -> Iterator[np.ndarray]:
    """§7.2 flow sizes at fleet scale, as dense [chunk_t, num_groups] float32
    blocks: group g draws lognormal(mu_g, sigma_g) items with mu ~ U(5.5, 9)
    and sigma ~ U(0.8, 1.4). One float32 normal draw per item,
    exponentiated in place."""
    rng = rng or np.random.default_rng(1)
    mu = rng.uniform(*mu_range, num_groups).astype(np.float32)
    sigma = rng.uniform(*sigma_range, num_groups).astype(np.float32)
    for _ in range(num_chunks):
        x = rng.standard_normal((chunk_t, num_groups), dtype=np.float32)
        x *= sigma
        x += mu
        np.exp(x, out=x)
        yield x


class Releaser:
    """Releases units 0, 1, 2, ... at `t0 + due[i]` on its own thread.

    `released` only grows, whatever the consumers do; `lag_s` records, per
    wake-up, how late the last unit it released was. `stop()` ends the
    schedule early (a backlog that never runs dry ends with the window).
    """

    # It wakes at most every MIN_SLEEP_S, releasing what fell due meanwhile,
    # so a fast schedule does not take the interpreter from the system.
    MIN_SLEEP_S = 5e-4
    MAX_SLEEP_S = 0.05

    def __init__(self, due: np.ndarray, t0: float):
        self.due = np.asarray(due, np.float64)
        self.t0 = float(t0)
        self.released = 0
        self.lag_s = []
        self._cond = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._run, name="releaser",
                                        daemon=True)

    def start(self) -> "Releaser":
        self._thread.start()
        return self

    def _run(self) -> None:
        n = len(self.due)
        while self.released < n and not self._stop:
            now = time.perf_counter() - self.t0
            upto = int(np.searchsorted(self.due, now, side="right"))
            if upto > self.released:
                with self._cond:
                    self.released = upto
                    self._cond.notify_all()
                self.lag_s.append(now - float(self.due[upto - 1]))
            if upto >= n:
                break
            wait = float(self.due[upto]) - now
            time.sleep(min(max(wait, self.MIN_SLEEP_S), self.MAX_SLEEP_S))
        with self._cond:
            self._stop = True
            self._cond.notify_all()

    def wait_for(self, i: int) -> bool:
        """Block until unit `i` is released; False once the schedule ended
        without it."""
        with self._cond:
            while self.released <= i and not self._stop:
                self._cond.wait()
            return self.released > i

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()

    def join(self, timeout: float = 10.0) -> None:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise RuntimeError("release thread did not stop")

    def lag_summary(self) -> dict:
        lag = np.asarray(self.lag_s or [0.0]) * 1e3
        return {"generator_wakeups": len(self.lag_s),
                "generator_lag_p99_ms": float(np.percentile(lag, 99)),
                "generator_lag_max_ms": float(lag.max())}
