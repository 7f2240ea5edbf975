"""Closed loop of one trainer sending bursts of small problems to the
eigensolver service and waiting for the whole burst.

Traffic parameters (``bench/traffic/<name>.json``):
  burst   -- problems per burst; the pool is one burst, drawn from the
             seed and sent in the same order every time;
  request -- problems per request: the burst goes out as
             ``burst / request`` ``solve_batch_async`` requests of
             consecutive problems, one after another;
  dtype   -- the requests' dtype (the problems are made in float64);
  knobs   -- keyword arguments of ``EigensolverClient.solve_batch_async``.
The configuration gives the problems (``generate.slq_problems``: m,
operator_dim, outliers, outlier_range, bulk_std, operator_seed) and the
service's ``ServeConfig`` (``serve``).

Every problem's latency is its request's: from just before the call to
the moment its future resolves.  Set-up repeats the burst until one
burst compiles or loads no program (at most ``warm_bursts`` times).
"""

from __future__ import annotations

import sys
import time

import numpy as np

from bench import generate
from bench.harness import Window, knobs


class Driver:
    def __init__(self, cell, seed: int):
        tr, cfg = cell.traffic, cell.config
        self.dtype_name = tr["dtype"]
        alpha, beta = generate.slq_problems(
            seed, int(tr["burst"]), int(cfg["m"]), int(cfg["operator_dim"]),
            int(cfg["outliers"]), cfg["outlier_range"],
            float(cfg["bulk_std"]), int(cfg["operator_seed"]))
        self.problems = [
            (a.astype(self.dtype_name).astype(np.float64),
             b.astype(self.dtype_name).astype(np.float64))
            for a, b in zip(alpha, beta)]
        self.request = int(tr.get("request", 1))
        if self.request < 1 or len(self.problems) % self.request:
            raise ValueError(f"burst {len(self.problems)} is not a whole "
                             f"number of requests of {self.request}")
        self.knobs = knobs(tr.get("knobs", {}))
        self.serve = dict(cfg.get("serve", {}))
        self.warm_bursts = int(tr.get("warm_bursts", 4))

    def setup(self, clock):
        import jax

        from repro.serve import EigensolverClient, ServeConfig
        self._jax = jax
        self._client = EigensolverClient(config=ServeConfig(**self.serve))
        r = self.request
        self._requests = [
            tuple(np.stack([p[k] for p in self.problems[i:i + r]])
                  .astype(self.dtype_name) for k in (0, 1))
            for i in range(0, len(self.problems), r)]
        for i in range(self.warm_bursts):
            before, flushes = clock.programs, self._counts()[0]
            t = time.perf_counter()
            self._burst(self.knobs)
            print(f"warm burst {i}: {time.perf_counter() - t:.3f} s, "
                  f"{self._counts()[0] - flushes} flushes, "
                  f"{clock.programs - before} programs compiled or loaded",
                  file=sys.stderr, flush=True)
            if clock.programs == before:
                break

    def _burst(self, kw: dict):
        """Send the pool as one burst and wait for all of it; returns
        [(index, eigenvalues or exception, latency s)]."""
        note = self._jax.profiler.TraceAnnotation
        r = self.request
        sent = [0.0] * len(self._requests)
        done = [0.0] * len(self._requests)
        futs = []
        with note("bench.submit"):
            for j, (d, e) in enumerate(self._requests):
                sent[j] = time.perf_counter()
                f = self._client.solve_batch_async(d, e, **kw)
                f.add_done_callback(
                    lambda _, j=j: done.__setitem__(j, time.perf_counter()))
                futs.append(f)
        out = []
        with note("bench.wait"):
            for j, f in enumerate(futs):
                try:
                    lams = list(np.asarray(f.result().eigenvalues))
                except Exception as exc:  # counted as failed answers
                    lams = [exc] * r
                out += [(j * r + k, lam, done[j] - sent[j])
                        for k, lam in enumerate(lams)]
        return out

    def _counts(self) -> tuple[int, int]:
        rows = self._client.metrics()["buckets"].values()
        return (sum(r["flushes"] for r in rows),
                sum(r["problems"] for r in rows))

    def window(self, seconds: float) -> Window:
        note = self._jax.profiler.TraceAnnotation
        answers, latencies = [], []
        flushes0, problems0 = self._counts()
        start = time.perf_counter()
        deadline = start + seconds
        with note("bench.window"):
            while True:
                for i, lam, lat in self._burst(self.knobs):
                    answers.append((i, lam))
                    latencies.append(lat)
                if time.perf_counter() >= deadline:
                    break
        wall = time.perf_counter() - start
        flushes, problems = self._counts()
        return Window(seconds=wall, problems=len(answers),
                      latencies_s=latencies, answers=answers,
                      counters={"flushes": flushes - flushes0,
                                "flushed_problems": problems - problems0})

    def answers_with(self, spec: dict) -> list:
        """(index, eigenvalues) of one burst sent with the knobs ``spec``
        in place of the traffic's (the control's path)."""
        return [(i, lam) for i, lam, _ in self._burst(knobs(spec))]

    def close(self):
        self._client.close()
        self._client = None
