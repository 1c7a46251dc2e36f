"""Static analysis gate: plan/schedule verifier sweep + hot-path lint.

Usage:

    PYTHONPATH=src python scripts/analyze.py            # CI configuration
    PYTHONPATH=src python scripts/analyze.py --full     # uncapped scenes
    PYTHONPATH=src python scripts/analyze.py --json     # machine-readable

Two gates, both exit-1 on any finding:

  verify   every VMEM-feasible (schedule, blocking) point of every
           fprop/dgrad/wgrad scene of the six paper CNNs is abstractly
           evaluated (``repro.analysis.verify``) — index-map coverage,
           sentinel taps, VMEM budget, dtype promotion, MAC agreement —
           without executing a single kernel.
  lint     ``repro.analysis.lint`` over ``src/repro`` — public asserts,
           metric-name namespace, computed span args on the serving/plan
           hot path, bare/unreviewed broad excepts.

The verifier sweep caches per-(scene, op) clean verdicts keyed by a
digest of the verifier-relevant sources, so an unchanged tree re-checks
nothing and a kernel/plan edit invalidates exactly everything (CI
persists the cache file across runs via actions/cache).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.analysis.lint import lint_paths                   # noqa: E402
from repro.analysis.verify import sweep_scene                # noqa: E402
from repro.models.cnn import cnn_layer_scenes                # noqa: E402
from repro.plan import ConvOp                                # noqa: E402

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SRC = os.path.join(_REPO, "src", "repro")

#: Sources whose semantics the verifier's verdicts depend on.  Editing any
#: of these invalidates the whole sweep cache.
_DIGEST_FILES = (
    "analysis/verify.py", "analysis/footprint.py", "kernels/mg3m_conv.py",
    "plan/build.py", "tune/space.py", "core/scene.py", "core/mapping.py",
    "models/cnn.py",
)

_OPS = (ConvOp.FPROP, ConvOp.DGRAD, ConvOp.WGRAD)


def _source_digest() -> str:
    h = hashlib.sha256()
    for rel in _DIGEST_FILES:
        with open(os.path.join(_SRC, rel), "rb") as f:
            h.update(rel.encode())
            h.update(f.read())
    return h.hexdigest()


def _load_cache(path: str, digest: str) -> set:
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("digest") == digest:
            return set(data.get("clean", []))
    except (OSError, ValueError):
        pass
    return set()


def _save_cache(path: str, digest: str, clean: set) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"digest": digest, "clean": sorted(clean)}, f)
    os.replace(tmp, path)


def run_verify(args) -> tuple:
    """Returns (findings, points_checked, points_cached)."""
    if args.full:
        scenes = cnn_layer_scenes(batch=args.batch)
    else:
        scenes = cnn_layer_scenes(batch=args.batch, max_hw=args.max_hw,
                                  max_ch=args.max_ch)
    digest = _source_digest()
    clean = set() if args.no_cache else _load_cache(args.cache, digest)
    findings, checked, cached = [], 0, 0
    for name, scene in sorted(scenes.items()):
        for op in _OPS:
            key = f"{scene.describe()}|{op.value}"
            if key in clean:
                cached += 1
                continue
            fnd, n = sweep_scene(scene, ops=(op,))
            checked += n
            if fnd:
                findings.extend(fnd)
            else:
                clean.add(key)
    if not args.no_cache:
        _save_cache(args.cache, digest, clean)
    return findings, checked, cached


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("--full", action="store_true",
                    help="uncapped paper scenes (slow; default caps "
                         "preserve stride/pad/remainder structure)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-hw", type=int, default=56,
                    help="cap spatial extent of swept scenes")
    ap.add_argument("--max-ch", type=int, default=128,
                    help="cap channel counts of swept scenes")
    ap.add_argument("--cache", default=os.path.join(
        _REPO, ".cache", "analyze_cache.json"))
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--skip-verify", action="store_true")
    ap.add_argument("--skip-lint", action="store_true")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    t0 = time.time()
    verify_findings, checked, cached = ([], 0, 0)
    if not args.skip_verify:
        verify_findings, checked, cached = run_verify(args)
    lint_findings = [] if args.skip_lint else lint_paths(_SRC)

    if args.json:
        print(json.dumps({
            "verify": [f.__dict__ for f in verify_findings],
            "lint": [f.__dict__ for f in lint_findings],
            "points_checked": checked, "points_cached": cached,
            "elapsed_s": round(time.time() - t0, 2),
        }, indent=2))
    else:
        for f in verify_findings:
            print(f"verify: [{f.code}] ({f.severity}) {f.message}")
        for f in lint_findings:
            print(f"lint: {f}")
        print(f"analyze: {checked} points checked, {cached} op-sweeps "
              f"cached, {len(verify_findings)} verify + "
              f"{len(lint_findings)} lint findings "
              f"in {time.time() - t0:.1f}s")
    return 1 if (verify_findings or lint_findings) else 0


if __name__ == "__main__":
    sys.exit(main())
