"""Serving engine: the worker loop that turns flush batches into device
launches and demuxes results back onto request futures.

One daemon thread owns the device:

    next_flush -> stage (host pack + pad) -> launch (dispatch)
               -> [stage/launch the NEXT flush]  -> finish (wait + demux)

Staging and launching of flush i+1 overlap the device execution of flush
i (double buffering) only where ``_launch`` returns before the device is
done: JAX dispatch is asynchronous, and ``_finish`` blocks on the
previous flush's arrays only after the next one is in flight.  A
mixed-precision flush (the default float64 route on a TPU) does not
overlap.  Its launch runs the host-driven certify/polish
(``bisect.refine_clusters``), which reads every polish launch's outputs
on the host, so ``_launch`` returns only after the flush's device work.
The profiler trace shows it: flush i's ``repro.polish.*`` spans lie
inside its ``repro.serve.launch``, and flush i+1's ``repro.serve.stage``
starts after them.  The flushes of a burst then run one after another,
and each waits in the queue behind the launches before it.  Each step
above is a span (``repro.serve.<step>``) sharing the flush's index.

Mixed-n solve flushes are host-padded to the group's common padded width
with ``_host_pad`` -- a numpy mirror of ``br_dc._pad_problem``'s
decoupled-sentinel construction (kept bitwise identical; pinned by
tests), so every problem's padded rows are exactly the rows its sync
solve would have produced internally and service results stay bit-for-bit
equal to the sync API.  Each problem's own boundary row rides the traced
track slot (``SolvePlan.execute(orig_n=...)``).

Reliability comes from the ``repro.runtime`` substrate: a
:class:`~repro.runtime.watchdog.Watchdog` heartbeats once per flush, a
per-bucket :class:`~repro.runtime.straggler.StragglerMonitor` flags slow
flushes against the bucket's own timing baseline, and
:func:`~repro.runtime.retry.retry_transient` retries transient device
errors.  A flush that still fails falls back to solving its requests one
by one, so a poisoned request fails alone and its flushmates complete.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from concurrent.futures import InvalidStateError

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import guard as _guard
from repro.core import plan as _plan
from repro.core import tune as _tune
from repro.core.instrument import span
from repro.core.request import SolveResult, _finalize_lanes, execute_request
from repro.runtime import StragglerMonitor, Watchdog, retry_transient
from repro.runtime import faults as _faults
from repro.runtime.retry import TRANSIENT_DEFAULT
from repro.serve.metrics import ServeMetrics, bucket_label
from repro.serve.scheduler import CoalescingScheduler, ServeConfig


def _resolve_future(future, result=None, exc=None) -> None:
    """Resolve a request future, tolerating callers that cancelled (or a
    fallback re-resolving members a partial demux already set): an
    InvalidStateError here must never escape into the worker loop -- a
    dead engine thread would hang every subsequent request forever."""
    try:
        if future.done():
            return
        if exc is not None:
            future.set_exception(exc)
        else:
            future.set_result(result)
    except InvalidStateError:
        pass


def _host_pad(d: np.ndarray, e: np.ndarray, N: int):
    """Pad (B, n) problems to width N with decoupled sentinel blocks.

    Bitwise mirror of ``br_dc._pad_problem`` (numpy instead of jnp so
    staging costs no device dispatches): sentinel diagonal entries sit
    above each problem's own Gershgorin bound, couplings into the padded
    region are exactly zero.  Returns (d_pad (B, N), e_pad (B, N-1)).
    """
    B, n = d.shape
    if n == N:
        return d, e
    emax = (np.max(np.abs(e), axis=1) if e.shape[1]
            else np.zeros((B,), d.dtype))
    # dtype-typed constants: NumPy 1.x value-based promotion silently
    # lifts `2.0 * f32_array` to f64, which would stage f32 traffic
    # through an f64 sentinel row (bitwise identical for f64 batches,
    # a silent promotion for f32/mixed ones).
    two = d.dtype.type(2.0)
    one = d.dtype.type(1.0)
    sentinel = np.max(np.abs(d), axis=1) + two * emax + one
    d_pad = np.concatenate(
        [d, np.broadcast_to(sentinel[:, None], (B, N - n)).astype(d.dtype)],
        axis=1)
    e_pad = np.concatenate([e, np.zeros((B, N - n), d.dtype)], axis=1)
    return d_pad, e_pad


def _flush_ready(flush: "_Flush") -> bool:
    """True when finishing the flush would not block (device done or the
    flush already failed); conservative True for results that are not
    lazy jax arrays (direct-path SolveResults may hold numpy)."""
    if flush.error is not None:
        return True
    obj = getattr(flush.result, "eigenvalues", flush.result)
    is_ready = getattr(obj, "is_ready", None)
    return True if is_ready is None else bool(is_ready())


class _Flush:
    """One staged flush: the launch inputs plus everything needed to
    demux device outputs back onto the member requests.  ``cert`` holds
    the flush-wide certificate mask (one batched Sturm sweep over the
    padded flush) when the route carries ``certify=True``.  ``seq`` is
    the flush's index in its spans; ``queue_wait_us`` is the time from
    its oldest member's submit to its assembly."""
    __slots__ = ("batch", "route", "label", "result", "error", "t_launch",
                 "cert", "seq", "problems", "queue_wait_us")

    def __init__(self, batch, route, label, seq, queue_wait_us):
        self.batch = batch
        self.route = route
        self.label = label
        self.result = None
        self.error: BaseException | None = None
        self.t_launch = 0.0
        self.cert = None
        self.seq = seq
        self.problems = sum(p.problems for p in batch)
        self.queue_wait_us = queue_wait_us


class ServeEngine:
    """Owns the worker thread, the watchdog, and per-bucket monitors."""

    def __init__(self, scheduler: CoalescingScheduler,
                 config: ServeConfig | None = None,
                 metrics: ServeMetrics | None = None):
        self.scheduler = scheduler
        self.config = config or scheduler.config
        self.metrics = metrics or scheduler.metrics
        hb = self.config.heartbeat_path or os.path.join(
            tempfile.gettempdir(), f"repro-serve-heartbeat-{os.getpid()}.json")
        self._watchdog = Watchdog(hb, timeout_s=self.config.watchdog_timeout_s)
        self._stragglers: dict[str, StragglerMonitor] = {}
        self._thread: threading.Thread | None = None
        self._flush_index = 0
        self._flush_seq = 0
        self._last_beat = 0.0
        self._beat_warned = False

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "ServeEngine":
        if self._thread is None:
            self._watchdog.start()
            self._thread = threading.Thread(
                target=self._loop, name="repro-serve-engine", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        """Drain the queue (the scheduler is closed first) and join."""
        self.scheduler.close()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        self._watchdog.stop()

    # --------------------------------------------------------------- loop

    def _loop(self) -> None:
        inflight: _Flush | None = None
        while True:
            if (inflight is None and self.scheduler.closed
                    and self.scheduler.pending_problems() == 0):
                return
            try:
                inflight = self._loop_once(inflight)
            except Exception as exc:
                # The worker thread must survive ANYTHING -- a dead
                # engine hangs every queued and future request forever
                # with zero errors reported.  Resolve whatever flush was
                # in flight (fallback skips already-done futures) and
                # keep serving.
                if inflight is not None:
                    for p in inflight.batch:
                        _resolve_future(p.future, exc=exc)
                    inflight = None
                else:
                    # Nothing to fail -- but never drop the evidence.
                    print(f"[serve] engine loop error (no flush in "
                          f"flight): {exc!r}", flush=True)

    def _loop_once(self, inflight: _Flush | None) -> _Flush | None:
        # Non-blocking poll while a flush is in flight (so it can be
        # finished the moment no follow-up work is due); short waits
        # otherwise to notice close/drain quickly.
        timeout = 0.0 if inflight is not None else 0.05
        with span("repro.serve.next_flush"):
            batch = self.scheduler.next_flush(timeout=timeout)
        assembled = time.monotonic()
        if batch is not None:
            # Flush assembly is the first point the engine owns the
            # requests: fail the ones whose deadline_ms budget ran out
            # while they were queued, so they never hold a launch slot.
            batch = self._reap_expired(batch)
        if not batch:
            if inflight is not None:
                self._finish_safely(inflight)
            else:
                self._idle_beat()
            return None
        if inflight is not None and _flush_ready(inflight):
            # Device already done: finish first so the flush's timing
            # (and its waiters' latency) don't absorb the next flush's
            # staging cost.
            self._finish_safely(inflight)
            inflight = None
        flush = self._stage_and_launch(batch, assembled)
        if inflight is not None:
            self._finish_safely(inflight)
        return flush

    def _finish_safely(self, flush: _Flush) -> None:
        """_finish with a last-resort guard: no matter what the finish
        bookkeeping does, every member future ends up resolved and the
        exception never reaches the worker loop with another flush in
        flight."""
        try:
            self._finish(flush)
        except Exception as exc:
            flush.error = exc
            try:
                self._fallback(flush)
            except Exception:
                for p in flush.batch:
                    _resolve_future(p.future, exc=exc)

    def _idle_beat(self) -> None:
        """Keep the heartbeat fresh while the service is merely idle --
        the Watchdog protocol means 'worker thread alive', not 'traffic
        present', so an external supervisor must not restart a healthy
        but quiet server."""
        now = time.monotonic()
        if now - self._last_beat >= min(30.0,
                                        self.config.watchdog_timeout_s / 4):
            self._beat(idle=True)

    def _beat(self, **info) -> None:
        self._last_beat = time.monotonic()
        try:
            self._watchdog.beat(self._flush_index, **info)
        except OSError as exc:
            # An unwritable heartbeat path degrades monitoring, never
            # serving (and must never kill the worker thread).
            if not self._beat_warned:
                self._beat_warned = True
                print(f"[serve] heartbeat write failed ({exc!r}); "
                      f"watchdog protocol degraded", flush=True)

    # ------------------------------------------------------------- stages

    def _stage_and_launch(self, batch, assembled: float) -> _Flush:
        """Stage + dispatch one flush assembled (``time.monotonic()``) at
        ``assembled``; JAX dispatch is async so this returns while the
        device still computes -- unless the route's host-driven polish
        blocks on it.  Errors (including any raised at dispatch) are
        handled in _finish, whose relaunch path owns the transient-retry
        budget -- execution faults only surface at block_until_ready
        there, so that is where retrying belongs."""
        route = batch[0].routed.route
        oldest = min(p.submit_t for p in batch)
        flush = _Flush(batch, route, bucket_label(route),
                       seq=self._flush_seq,
                       queue_wait_us=(assembled - oldest) * 1e6)
        self._flush_seq += 1
        flush.t_launch = time.perf_counter()
        try:
            # Chaos site "serve.stage": a delay here stalls staging (the
            # straggler monitor and watchdog see it); an error demotes
            # the flush to the retry/fallback path like any staging bug.
            _faults.inject("serve.stage")
            flush.result = self._launch(flush)
        except Exception as exc:   # retried/isolated in _finish
            flush.error = exc
        return flush

    def _reap_expired(self, batch):
        now = time.monotonic()
        live = []
        for p in batch:
            if p.expired(now):
                self._fail_deadline(p, bucket_label(p.routed.route))
            else:
                live.append(p)
        return live

    def _fail_deadline(self, p, label: str) -> None:
        self.metrics.record_deadline(label)
        self.metrics.record_error(label)
        _guard.DEADLINES.increment()
        waited_ms = (time.monotonic() - p.submit_t) * 1e3
        _resolve_future(p.future, exc=_guard.DeadlineExceeded(
            f"request expired: deadline_ms="
            f"{p.routed.request.deadline_ms:g} budget exhausted "
            f"({waited_ms:.1f} ms since submit)"))

    def _launch_and_wait(self, flush: _Flush):
        result = self._launch(flush)
        jax.block_until_ready(getattr(result, "eigenvalues", result))
        return result

    def _launch(self, flush: _Flush):
        # Chaos site "serve.launch": hit once per launch *attempt*, so a
        # count-driven schedule can fail the first dispatch and let the
        # transient-retry relaunch succeed (or keep failing to force the
        # per-request fallback).
        with span("repro.serve.launch", flush=flush.seq):
            _faults.inject("serve.launch")
            route = flush.route
            if isinstance(route, _plan.PlanKey):
                return self._launch_solve(flush)
            if isinstance(route, _plan.RangePlanKey):
                return self._launch_range(flush)
            # Direct (uncoalescable) request: the sync path, one launch.
            return execute_request(flush.batch[0].routed)

    def _stage_span(self, flush: _Flush):
        """The span of a flush's host packing and padding."""
        return span("repro.serve.stage", flush=flush.seq,
                    problems=flush.problems,
                    queue_wait_us=flush.queue_wait_us)

    def _launch_solve(self, flush: _Flush):
        route = flush.route
        N = route.padded_n
        with self._stage_span(flush):
            ds, es, orig_n = [], [], []
            for p in flush.batch:
                d = np.asarray(p.routed.d)
                e = np.asarray(p.routed.e)
                d, e = _host_pad(d, e, N)
                ds.append(d)
                es.append(e)
                orig_n.extend([p.routed.n] * p.routed.batch)
            d_all = np.concatenate(ds, axis=0)
            e_all = np.concatenate(es, axis=0)
        plan = _plan.plan_for_route(route, d_all.shape[0])
        res = plan.execute(d_all, e_all,
                           orig_n=np.asarray(orig_n, np.int32))
        if route.certify:
            # One batched Sturm sweep certifies the WHOLE flush against
            # the padded inputs.  Bit-equivalent to each member's sync
            # certificate: padding is decoupled (zero couplings, sentinel
            # rows above the Gershgorin bound) so counts at real targets
            # are unchanged, and the executor masks sentinel rows out of
            # the per-problem tolerance norm.  Dispatch is async -- demux
            # materializes the mask alongside the eigenvalues.
            from repro.core import bisect as _bis
            lam = res.eigenvalues
            dj = jnp.asarray(d_all)
            ej = jnp.asarray(e_all)
            flush.cert = _bis._certify_executor(
                dj, ej * ej, lam, jnp.asarray(orig_n, jnp.int32),
                jnp.asarray(route.refine_tol, dj.dtype))[0]
        return res

    def _launch_range(self, flush: _Flush):
        with self._stage_span(flush):
            d_all = np.concatenate([np.asarray(p.routed.d)
                                    for p in flush.batch], axis=0)
            e_all = np.concatenate([np.asarray(p.routed.e)
                                    for p in flush.batch], axis=0)
            # routed.il is a scalar window start for plain range requests
            # and a per-problem (batch,) array for kind="edges" (duplicated
            # rows, bottom-k + top-k windows); broadcast_to handles both,
            # so edges probes and ordinary sliced traffic mix freely in
            # one flush.
            il = np.concatenate([np.broadcast_to(np.asarray(p.routed.il),
                                                 (p.routed.batch,))
                                 for p in flush.batch])
            k = max(p.routed.k for p in flush.batch)
        plan = _plan.range_plan_for_route(flush.route, d_all.shape[0])
        return plan.execute(d_all, e_all, il, k)

    # ------------------------------------------------------------- finish

    def _finish(self, flush: _Flush) -> None:
        if flush.error is None:
            try:
                with span("repro.serve.wait", flush=flush.seq):
                    jax.block_until_ready(
                        getattr(flush.result, "eigenvalues", flush.result))
            except Exception as exc:
                flush.error = exc
        if (flush.error is not None and self.config.retries > 0
                and isinstance(flush.error, TRANSIENT_DEFAULT)):
            # Transient device faults (preemption, flaky interconnect)
            # surface either at dispatch or at block_until_ready; give
            # the whole launch+wait the configured retry budget before
            # demoting the flush to per-request fallback.  Errors outside
            # the transient classes (ValueError etc.) skip straight to
            # fallback -- relaunching a whole coalesced batch on a
            # deterministic failure would head-of-line block every other
            # bucket for retries * backoff.
            self.metrics.record_retry(flush.label)
            relaunch = retry_transient(
                self._launch_and_wait, retries=self.config.retries - 1,
                backoff_s=self.config.retry_backoff_s,
                on_retry=lambda i, exc: self.metrics.record_retry(
                    flush.label))
            try:
                flush.result = relaunch(flush)
                flush.error = None
            except Exception as exc:
                flush.error = exc
        if flush.error is not None:
            self._fallback(flush)
            return
        duration = time.perf_counter() - flush.t_launch
        try:
            with span("repro.serve.demux", flush=flush.seq,
                      requests=len(flush.batch)):
                self._demux(flush)
        except Exception as exc:
            flush.error = exc
            self._fallback(flush)
            return
        problems = flush.problems
        self.metrics.record_flush(flush.label, len(flush.batch), problems,
                                  tuned=bool(_tune.serve_knobs(flush.label)))
        now = time.monotonic()
        for p in flush.batch:
            self.metrics.record_latency(flush.label, now - p.submit_t)
        self._flush_index += 1
        self._beat(bucket=flush.label, requests=len(flush.batch),
                   problems=problems)
        mon = self._stragglers.get(flush.label)
        if mon is None:
            mon = self._stragglers[flush.label] = StragglerMonitor(
                window=self.config.straggler_window,
                threshold=self.config.straggler_threshold)
        mon.record(self._flush_index, duration)

    def _demux(self, flush: _Flush) -> None:
        # One host transfer per flushed output, numpy views per request:
        # slicing the (possibly device-sharded) batch arrays on device
        # would dispatch a gather per request -- measurably slower than
        # the serving win at small n.
        route = flush.route
        if isinstance(route, _plan.PlanKey):
            res = flush.result
            lam_all = np.asarray(res.eigenvalues)
            blo_all = None if res.blo is None else np.asarray(res.blo)
            bhi_all = None if res.bhi is None else np.asarray(res.bhi)
            cert_all = None if flush.cert is None else np.asarray(flush.cert)
            now = time.monotonic()
            off = 0
            for p in flush.batch:
                r = p.routed
                end = off + r.batch
                lam = lam_all[off:end, :r.n]
                blo = None if blo_all is None else blo_all[off:end, :r.n]
                bhi = None if bhi_all is None else bhi_all[off:end, :r.n]
                cert = None if cert_all is None else cert_all[off:end, :r.n]
                off = end
                if p.expired(now):
                    # Post-launch deadline check: the flush finished, but
                    # this member's budget ran out while it executed.
                    self._fail_deadline(p, flush.label)
                    continue
                try:
                    # Per-request degradation ladder -- the SAME
                    # finalizer the sync path runs, so a request gets one
                    # answer whether it ran alone or coalesced.  The
                    # host transfer above already paid for the finite
                    # check, so demux always screens for output poison.
                    lam, blo, bhi, diag = _finalize_lanes(
                        r, lam, blo, bhi, cert=cert, check_finite=True)
                except Exception as exc:
                    # A member whose ladder is exhausted fails ALONE; its
                    # flushmates keep demuxing.
                    self.metrics.record_error(flush.label)
                    _resolve_future(p.future, exc=exc)
                    continue
                if diag and diag.get("escalations"):
                    self.metrics.record_degradation(
                        flush.label,
                        lanes=sum(ev["lanes"]
                                  for ev in diag["escalations"]))
                if r.request.kind == "full":
                    lam = lam[0]
                    blo = None if blo is None else blo[0]
                    bhi = None if bhi is None else bhi[0]
                _resolve_future(p.future, SolveResult(
                    eigenvalues=lam, blo=blo, bhi=bhi,
                    kind=r.request.kind, method=r.request.method,
                    diagnostics=diag))
        elif isinstance(route, _plan.RangePlanKey):
            lam_all = np.asarray(flush.result)
            now = time.monotonic()
            off = 0
            for p in flush.batch:
                r = p.routed
                lam = lam_all[off:off + r.batch, :r.k]
                off += r.batch
                if p.expired(now):
                    self._fail_deadline(p, flush.label)
                    continue
                diag = None
                if r.scale != 1.0:
                    inv = np.dtype(lam.dtype).type(1.0 / r.scale)
                    lam = lam * inv
                    diag = {"equilibration_scale": r.scale}
                if r.request.certify:
                    # Bisection brackets every value with exact integer
                    # counts: certified by construction, no sweep needed
                    # (mirrors the sync range path).
                    diag = dict(diag or ())
                    diag.update(certified=int(r.batch * r.k),
                                lanes=int(r.batch * r.k))
                if r.single:
                    lam = lam[0]
                _resolve_future(p.future, SolveResult(
                    eigenvalues=lam, kind=r.request.kind,
                    method=r.request.method, diagnostics=diag))
        else:
            p = flush.batch[0]
            if p.expired(time.monotonic()):
                self._fail_deadline(p, flush.label)
            else:
                _resolve_future(p.future, flush.result)

    def _fallback(self, flush: _Flush) -> None:
        """Flush-level failure: isolate it -- re-run each member through
        the sync path so only genuinely poisoned requests fail."""
        self.metrics.record_fallback(flush.label)
        for p in flush.batch:
            if p.future.done():   # partial demux already resolved it
                continue
            if p.expired(time.monotonic()):
                self._fail_deadline(p, flush.label)
                continue
            try:
                result = execute_request(p.routed)
                jax.block_until_ready(result.eigenvalues)
                _resolve_future(p.future, result)
                self.metrics.record_latency(flush.label,
                                            time.monotonic() - p.submit_t)
            except Exception as exc:
                self.metrics.record_error(flush.label)
                _resolve_future(p.future, exc=exc)
        self._beat(bucket=flush.label, fallback=True,
                   requests=len(flush.batch))
