#!/usr/bin/env python
"""Import every ``repro.*`` module; exit nonzero on any failure.

The dependency-light contract: the whole package must import with only
jax + numpy + msgpack installed (hypothesis and zstandard are optional,
guarded at their use sites).  Run from anywhere:

    python tools/check_imports.py
"""
import importlib
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)

# repro.launch.dryrun/autotune pin the placeholder device count via
# XLA_FLAGS at import time; keep it tiny for the import check.
os.environ.setdefault("REPRO_DRYRUN_DEVICES", "2")

# Modules that must exist (guards against packages being dropped or renamed
# without this check noticing — the walk below only sees what's on disk).
REQUIRED = (
    "repro.compiler",
    "repro.compiler.cli",
    "repro.compiler.executor",
    "repro.compiler.executor.base",
    "repro.compiler.executor.pool",
    "repro.compiler.executor.remote",
    "repro.compiler.executor.stub",
    "repro.compiler.executor.wire",
    "repro.compiler.executor.worker",
    "repro.compiler.netopt",
    "repro.compiler.netopt.genetic",
    "repro.compiler.netopt.hwspace",
    "repro.compiler.netopt.loop",
    "repro.compiler.netopt.partition",
    "repro.compiler.netopt.report",
    "repro.compiler.oracle",
    "repro.compiler.records",
    "repro.compiler.report",
    "repro.compiler.session",
    "repro.compiler.surrogate_store",
    "repro.compiler.task",
    "repro.compiler.zoo",
    "repro.core.tuner",
    "repro.core.baselines",
    "repro.launch.autotune",
    "repro.obs",
    "repro.obs.export",
    "repro.obs.log",
    "repro.obs.metrics",
    "repro.obs.serve",
    "repro.obs.trace",
)


def iter_modules():
    pkg_root = os.path.join(SRC, "repro")
    for dirpath, _dirnames, filenames in os.walk(pkg_root):
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fname), SRC)
            mod = rel[:-3].replace(os.sep, ".")
            if mod.endswith(".__init__"):
                mod = mod[: -len(".__init__")]
            yield mod


def main() -> int:
    failures = []
    modules = sorted(set(iter_modules()))
    missing = [m for m in REQUIRED if m not in modules]
    if missing:
        print(f"MISSING required modules: {missing}", file=sys.stderr)
        return 1
    for mod in modules:
        try:
            importlib.import_module(mod)
        except Exception:
            failures.append((mod, traceback.format_exc()))
            print(f"FAIL  {mod}")
        else:
            print(f"ok    {mod}")
    print(f"\n{len(modules) - len(failures)}/{len(modules)} modules import "
          "cleanly")
    for mod, tb in failures:
        print(f"\n--- {mod} ---\n{tb}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
