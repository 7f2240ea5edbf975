"""Closed loop of one caller solving large tridiagonals one at a time.

Traffic parameters (``bench/traffic/<name>.json``):
  family  -- LAPACK test-set family (``bench.generate.family``);
  dtype   -- the request's dtype: the problem is generated in float64,
             rounded to it on the host, and solved in it;
  pool    -- distinct problems, drawn from the seed and solved in turn;
  knobs   -- keyword arguments of ``repro.core.eigvalsh_tridiagonal``
             (a ``dtype`` value is a numpy dtype name).
The size is the configuration's ``n_<dtype>``.

Each request is ``eigvalsh_tridiagonal(d, e, **knobs)`` on device arrays,
with its eigenvalues brought to the host; the next is issued when it
returns.  Set-up solves every problem of the pool once, which compiles
(or loads) every program the window runs.
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
        self.n = int(cfg["n_" + self.dtype_name])
        rng = np.random.default_rng(seed)
        # Each problem as served: rounded to the request's dtype.
        self.problems = [
            tuple(a.astype(self.dtype_name).astype(np.float64)
                  for a in generate.family(tr["family"], self.n, int(s)))
            for s in rng.integers(0, 2**62, int(tr["pool"]))]
        self.knobs = knobs(tr.get("knobs", {}))

    def setup(self, clock):
        import jax
        import jax.numpy as jnp

        from repro.core import eigvalsh_tridiagonal
        self._jax = jax
        self._solve = eigvalsh_tridiagonal
        self._dev = [(jnp.asarray(d.astype(self.dtype_name)),
                      jnp.asarray(e.astype(self.dtype_name)))
                     for d, e in self.problems]
        for k in range(len(self._dev)):
            before, t = clock.programs, time.perf_counter()
            self._call(k)
            print(f"warm solve {k}: {time.perf_counter() - t:.3f} s, "
                  f"{clock.programs - before} programs compiled or loaded",
                  file=sys.stderr, flush=True)

    def _call(self, k: int):
        d, e = self._dev[k]
        return np.asarray(self._solve(d, e, **self.knobs))

    def window(self, seconds: float) -> Window:
        note = self._jax.profiler.TraceAnnotation
        answers, latencies = [], []
        start = time.perf_counter()
        deadline = start + seconds
        i = 0
        with note("bench.window"):
            while True:
                k = i % len(self._dev)
                t = time.perf_counter()
                with note("bench.solve"):
                    try:
                        lam = self._call(k)
                    except Exception as exc:  # counted as a failed answer
                        lam = exc
                now = time.perf_counter()
                answers.append((k, lam))
                latencies.append(now - t)
                i += 1
                if now >= deadline:
                    break
        return Window(seconds=time.perf_counter() - start, problems=i,
                      latencies_s=latencies, answers=answers, counters={})

    def answers_with(self, spec: dict) -> list:
        """(index, eigenvalues) of every pool problem, solved once with the
        knobs ``spec`` in place of the traffic's (the control's path)."""
        kw = knobs(spec)
        return [(k, np.asarray(self._solve(d, e, **kw)))
                for k, (d, e) in enumerate(self._dev)]

    def close(self):
        self._dev = None
        self._solve = None
