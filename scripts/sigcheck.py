#!/usr/bin/env python
"""sigcheck CLI: static signal-protocol verification + determinism lint.

Runs entirely at trace time on CPU — no TPU, no kernel execution. Exit
status is 0 unless ``--fail-on-findings`` is set and any finding (or any
gallery miss) is reported. Output is one JSON document on stdout so CI and
the dryrun gate can parse it.

  python scripts/sigcheck.py --all --fail-on-findings   # the CI gate
  python scripts/sigcheck.py --op gemm_rs               # one op
  python scripts/sigcheck.py --gallery                  # checker self-test
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from triton_dist_tpu.utils.env import force_virtual_cpu_devices  # noqa: E402

# trace-time only, by design on the CPU: the migrate_pages determinism lint
# traces through shard_map on a 2-device mesh; everything else is
# device-count independent
force_virtual_cpu_devices(2)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--all", action="store_true",
                    help="check every registered op + the serving lint")
    ap.add_argument("--op", action="append", default=[],
                    help="check one registered op (repeatable)")
    ap.add_argument("--gallery", action="store_true",
                    help="run the broken-kernel gallery (checker self-test)")
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the serving-program determinism lint")
    ap.add_argument("--fail-on-findings", action="store_true",
                    help="exit 1 if any finding is reported")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the human summary on stderr")
    args = ap.parse_args()
    if not (args.all or args.op or args.gallery):
        ap.error("pick --all, --op NAME, or --gallery")

    from triton_dist_tpu.analysis import (check_gallery, check_registry,
                                          lint_serving_programs)

    t0 = time.monotonic()
    doc = {"ops": {}, "serving_lint": [], "gallery": {}}
    n_findings = 0
    gallery_misses = []

    if args.all or args.op:
        reports = check_registry(args.op or None)
        if args.op:
            unknown = [o for o in args.op if o not in reports]
            if unknown:
                print(f"unknown op(s): {unknown}", file=sys.stderr)
                return 2
        for name, rep in sorted(reports.items()):
            doc["ops"][name] = rep.to_json()
            n_findings += len(rep.findings)
            if not args.quiet and rep.findings:
                for f in rep.findings:
                    print(f"  {f}", file=sys.stderr)

    if (args.all and not args.no_lint):
        lint = lint_serving_programs()
        doc["serving_lint"] = [f.to_json() for f in lint]
        n_findings += len(lint)
        if not args.quiet:
            for f in lint:
                print(f"  {f}", file=sys.stderr)

    if args.gallery:
        for name, (expected, rep) in check_gallery().items():
            caught = expected in rep.finding_kinds
            doc["gallery"][name] = {"expected": expected, "caught": caught,
                                    "report": rep.to_json()}
            if not caught:
                gallery_misses.append(name)

    doc["elapsed_s"] = round(time.monotonic() - t0, 3)
    doc["n_findings"] = n_findings
    doc["gallery_misses"] = gallery_misses
    json.dump(doc, sys.stdout, indent=1)
    print()

    if not args.quiet:
        checked = sum(1 for r in doc["ops"].values() if not r["skipped"])
        skipped = len(doc["ops"]) - checked
        misses = gallery_misses or "none"
        print(f"sigcheck: {checked} ops checked, {skipped} skipped, "
              f"{n_findings} findings, gallery misses: {misses} "
              f"[{doc['elapsed_s']}s]", file=sys.stderr)

    if args.fail_on_findings and (n_findings or gallery_misses):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
