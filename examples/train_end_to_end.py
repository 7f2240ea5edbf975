"""End-to-end training: a real (reduced) model for a few hundred steps.

    PYTHONPATH=src python examples/train_end_to_end.py            # ~22M params
    PYTHONPATH=src python examples/train_end_to_end.py --full     # mamba2-130m
    PYTHONPATH=src python examples/train_end_to_end.py --smoke    # CI gate

Drives the same repro.launch.train stack used at scale: sharded step,
prefetching pipeline, atomic checkpoints with auto-resume, watchdog,
straggler stats, and spectral LR governance -- per-probe HVP -> Lanczos
-> sliced extremal eigensolve submitted through the serving layer
(kind='edges').

``--smoke`` is the CI gate: a bounded run with the serve-routed monitor
on that exits nonzero unless the loss improved, the governor probed at
least once, every applied lr scale stayed inside [min_scale, 1], and the
eigensolver service finished with zero errors.
"""

import argparse
import tempfile

from repro.launch.train import main as train_main
from repro.runtime.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="train the full mamba2-130m (TPU-scale) config")
    ap.add_argument("--smoke", action="store_true",
                    help="bounded CI gate: reduced config, serve-routed "
                         "spectral monitor, hard asserts")
    ap.add_argument("--steps", type=int, default=200)
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        with tempfile.TemporaryDirectory() as ckpt:
            report = train_main([
                "--arch", "qwen3-0.6b", "--smoke",
                "--steps", "65", "--batch", "8", "--seq", "128",
                "--lr", "3e-3",
                "--spectral-every", "30", "--serve-monitor",
                "--probe-steps", "4", "--probe-batch", "1",
                "--ckpt-dir", ckpt, "--ckpt-every", "100000",
                "--log-every", "20"])
        losses, scales = report["losses"], report["lr_scales"]
        serve = report["serve"]
        errors = sum(b["errors"] for b in serve["buckets"].values())
        overhead = (report["probe_seconds"] / report["step_seconds"]
                    if report["step_seconds"] else 0.0)
        print(f"\n[gate] loss {losses[0]:.4f} -> {losses[-1]:.4f}  "
              f"probes={report['probes']}  overhead={overhead:.1%}  "
              f"serve_errors={errors}")
        if losses[-1] >= losses[0]:
            raise SystemExit("gate FAILED: loss did not improve")
        if report["probes"] < 1:
            raise SystemExit("gate FAILED: governor never probed")
        if not all(report["min_scale"] <= s <= 1.0 for s in scales):
            raise SystemExit(f"gate FAILED: lr scale left "
                             f"[{report['min_scale']}, 1]: {scales}")
        if errors:
            raise SystemExit(f"gate FAILED: {errors} serve errors")
        print("[gate] PASS")
        return

    argv = ["--arch", "mamba2-130m",
            "--steps", str(args.steps),
            "--batch", "8", "--seq", "128",
            "--lr", "1e-3",
            "--spectral-every", "25", "--serve-monitor",
            "--probe-steps", "4", "--probe-batch", "1",
            "--ckpt-dir", "/tmp/repro_e2e_ckpt",
            "--ckpt-every", "50", "--log-every", "10"]
    if not args.full:
        argv.append("--smoke")
    report = train_main(argv)
    losses = report["losses"]
    print(f"\nfinal loss {losses[-1]:.4f} (started {losses[0]:.4f}) -- "
          f"{'improved' if losses[-1] < losses[0] else 'NOT improved'}; "
          f"probes={report['probes']} lam_max={report['lam_max']:.3e}")


if __name__ == "__main__":
    main()
