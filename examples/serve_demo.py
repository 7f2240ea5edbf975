"""Eigensolver-as-a-service demo: mixed workload through the coalescer.

    PYTHONPATH=src python examples/serve_demo.py [--requests 200] [--smoke]

Drives a mixed request stream -- full spectra at several sizes, a
stacked batch, top-k/bottom-k range slices -- from several submitter
threads through :class:`repro.serve.EigensolverClient`, then prints the
request lifecycle and the per-bucket metrics table (coalesce factor,
p50/p99 latency, plan-cache hits).

The lifecycle every request takes:

    submit   -> the client routes it to its bucketed compile-cache key
    route    -> equal keys are guaranteed to share one executable
    coalesce -> the scheduler groups pending requests per key until
                max_batch / max_wait_us / queue pressure fires
    flush    -> the engine launches ONE batched solve per group
                (mixed sizes host-padded, boundary rows tracked per
                problem) with watchdog + straggler + retry coverage
    demux    -> each future resolves to bit-for-bit the sync answer

``--smoke`` is the CI gate: exits non-zero unless every request
succeeded and same-bucket traffic actually coalesced (factor > 1).

``--chaos`` arms a scripted deterministic fault schedule
(``repro.runtime.faults``) before driving the same workload: a transient
launch fault (consumes one retry), NaN-poisoned device outputs (walks
the degradation ladder), and a staging delay (trips the straggler
clock).  The gate then also requires ZERO hung futures -- every future
resolves despite the storm -- and at least one recorded degradation.
"""

import argparse
import sys
import threading
import time

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=200,
                    help="total request count across all kinds")
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-us", type=int, default=3000)
    ap.add_argument("--smoke", action="store_true",
                    help="assert zero errors and coalesce factor > 1")
    ap.add_argument("--chaos", action="store_true",
                    help="inject a scripted fault schedule; assert zero "
                         "hung futures and recorded degradations")
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.serve import EigensolverClient

    sizes = (48, 56, 64)          # one shared padded bucket: N = 64

    def make(n, rng):             # caller supplies its own Generator --
        return rng.normal(size=n), rng.normal(size=n - 1)  # not thread-safe

    print("[serve] prewarming flush buckets (cold-start-free serving)...")
    b, spec = 1, []
    while b <= args.max_batch:
        # Every (bucket, flush-width) pair traffic can produce: the mixed
        # sizes share ONE padded solve bucket (N = 64), while range plans
        # key on exact n -- three buckets, all k-widths riding k_bucket 8.
        spec.append({"kind": "solve", "n": 64, "batch": b})
        spec += [{"kind": "range", "n": n, "k": 8, "batch": b}
                 for n in sizes]
        b *= 2
    client = EigensolverClient(max_batch=args.max_batch,
                               max_wait_us=args.max_wait_us,
                               queue_depth=4 * args.max_batch,
                               prewarm=spec)

    if args.chaos:
        # Armed AFTER prewarm so the schedule's hit counts line up with
        # request traffic, not compile-time dry runs.  Count-driven and
        # seeded: the same run injects the same faults every time.
        from repro.runtime import FaultSpec, configure_faults
        configure_faults([
            FaultSpec(site="serve.launch", kind="error", times=(0,),
                      error="transient"),
            FaultSpec(site="plan.output", kind="nan", times=(1, 4),
                      lane=0, width=1),
            FaultSpec(site="serve.stage", kind="delay", times=(3,),
                      delay_s=0.05),
        ])
        print("[serve] chaos schedule armed: serve.launch error, "
              "plan.output NaN x2, serve.stage delay")

    futs, lock = [], threading.Lock()

    def worker(widx):
        local_rng = np.random.default_rng(widx)
        out = []
        for i in range(args.requests // args.threads):
            n = sizes[(widx + i) % len(sizes)]
            d, e = make(n, local_rng)
            kind = (widx + i) % 4
            if kind < 2:                      # full spectrum
                out.append(client.solve_async(d, e))
            elif kind == 2:                   # top-8 slice
                out.append(client.solve_range_async(
                    d, e, select="i", il=n - 8, iu=n - 1))
            else:                             # bottom-5 slice (same k
                out.append(client.solve_range_async(  # bucket as top-8)
                    d, e, select="i", il=0, iu=4))
            if local_rng.random() < 0.2:
                time.sleep(0.001)             # bursty, not perfectly smooth
        with lock:
            futs.extend(out)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(args.threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    import concurrent.futures as _cf
    errors = hung = 0
    for f in futs:
        try:
            f.result(timeout=600)
        except _cf.TimeoutError:              # the one unforgivable sin
            hung += 1
            print("[serve] request HUNG (future never resolved)")
        except Exception as exc:  # noqa: BLE001 - demo counts, then reports
            errors += 1
            print(f"[serve] request failed: {exc!r}")
    dt = time.perf_counter() - t0

    snap = client.metrics()
    client.close()
    if args.chaos:
        from repro.runtime import fault_stats, reset_faults
        chaos_stats = fault_stats()
        reset_faults()

    print(f"\n[serve] {len(futs)} requests in {dt:.2f}s "
          f"({len(futs) / dt:.0f} req/s), {errors} errors")
    print(f"[serve] {'bucket':<28}{'req':>6}{'flush':>7}{'coal':>7}"
          f"{'p50ms':>8}{'p99ms':>8}{'err':>5}")
    coal_num = coal_den = 0
    for label, b in sorted(snap["buckets"].items()):
        print(f"[serve] {label:<28}{b['requests']:>6}{b['flushes']:>7}"
              f"{b['coalesce_factor']:>7.1f}{b['latency_p50_ms']:>8.1f}"
              f"{b['latency_p99_ms']:>8.1f}{b['errors']:>5}")
        coal_num += b["problems"]
        coal_den += b["flushes"]
    cache = snap["plan_cache"]
    overall = coal_num / max(coal_den, 1)
    print(f"[serve] overall coalesce factor: {overall:.2f}x")
    print(f"[serve] plan cache: {cache['size']} solve + "
          f"{cache['range_size']} range plans, "
          f"{cache['hits'] + cache['range_hits']} hits, "
          f"{cache['executor_traces'] + cache['range_executor_traces']} "
          f"traces, {(cache['state_bytes'] + cache['range_state_bytes']) / 1e6:.2f} MB state budget")

    if args.chaos:
        degr = sum(b.get("degradations", 0)
                   for b in snap["buckets"].values())
        retries = sum(b.get("retries", 0)
                      for b in snap["buckets"].values())
        print(f"[serve] chaos: fired={chaos_stats['fired']}, "
              f"degradations={degr}, retries={retries}, hung={hung}")

    if args.smoke:
        ok = errors == 0 and hung == 0 and overall > 1.0
        if args.chaos:
            ok = ok and degr >= 1
            print(f"[serve] chaos smoke: {'PASS' if ok else 'FAIL'} "
                  f"(errors={errors}, hung={hung}, degradations={degr})")
        else:
            print(f"[serve] smoke: {'PASS' if ok else 'FAIL'} "
                  f"(errors={errors}, coalesce={overall:.2f})")
        if not ok:
            sys.exit(1)


if __name__ == "__main__":
    main()
