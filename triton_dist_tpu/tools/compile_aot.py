"""Build a persisted AOT serving artifact from an ``ArtifactSpec`` JSON
(the parity target for the reference's ``tools/compile_aot.py`` AOT kernel
sweep — here the unit is not a kernel list but the full compiled-program
set of a declared serving fleet; see docs/serving.md "Zero-trace cold
start").

Usage::

    python -m triton_dist_tpu.tools.compile_aot --spec spec.json \
        --out /path/to/artifact [--registry tuned.json] [--devices N]

    # no --spec: build the built-in tiny smoke spec (CPU CI round trip)
    python -m triton_dist_tpu.tools.compile_aot --out /tmp/artifact --tiny

The build pays every fresh trace so no replica cold start ever does; the
resulting directory is what ``serve_sim.py --artifact`` /
``cluster_sim.py --artifact`` and ``ServingEngine(artifact=...)`` load.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

TINY_SPEC = {
    "model": {"kind": "llama", "vocab_size": 128, "d_model": 32,
              "n_layers": 1, "n_heads": 2, "n_kv_heads": 1, "d_ff": 64,
              "max_seq_len": 64, "dtype": "float32"},
    "engines": [{"kind": "colocated", "num_slots": 2, "page_size": 8,
                 "num_pages": 32, "pages_per_seq": 8, "prefill_chunk": 8}],
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="AOT-compile a serving fleet's full program set into a "
                    "persisted artifact directory")
    ap.add_argument("--spec", help="ArtifactSpec JSON file")
    ap.add_argument("--tiny", action="store_true",
                    help="use the built-in tiny colocated smoke spec")
    ap.add_argument("--out", required=True, help="artifact output directory")
    ap.add_argument("--registry",
                    help="tuned-config registry JSON to embed (the file "
                         "tools/tune_serving.py writes)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual CPU devices before compiling "
                         "(0 = leave the backend alone)")
    args = ap.parse_args(argv)

    from triton_dist_tpu.utils.env import (configure_compile_cache,
                                           force_virtual_cpu_devices)
    configure_compile_cache()
    if args.devices:
        force_virtual_cpu_devices(args.devices)

    if args.spec:
        with open(args.spec, encoding="utf-8") as f:
            spec_doc = json.load(f)
    elif args.tiny:
        spec_doc = TINY_SPEC
    else:
        ap.error("pass --spec FILE or --tiny")

    from triton_dist_tpu.aot import (ArtifactSpec, TunedConfigRegistry,
                                     build_artifact)
    spec = ArtifactSpec.from_json(spec_doc)
    registry = (TunedConfigRegistry.load(args.registry)
                if args.registry else None)

    t0 = time.time()
    build_artifact(spec, args.out, registry=registry,
                   log=lambda s: print(s, file=sys.stderr))
    dt = time.time() - t0

    with open(os.path.join(args.out, "MANIFEST.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    n_prog = sum(len(v) for v in manifest["programs"].values())
    print(json.dumps({
        "out": args.out,
        "spec_digest": manifest["spec_digest"],
        "engines": sorted(manifest["programs"].keys()),
        "programs": n_prog,
        "registry_entries": len(registry) if registry else 0,
        "build_s": round(dt, 3),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
