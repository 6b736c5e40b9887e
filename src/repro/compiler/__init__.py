"""``repro.compiler`` — the unified tuning-session API.

One seam over both tuning stacks: the conv/analytical path (paper Fig. 2)
and the pod-level compile path (beyond-paper §Perf) both run as a
:class:`Session` over :class:`TuningTask`\\ s measured through one memoizing
:class:`Oracle`, sharing a GBT cost model across tasks and persisting /
resuming from JSONL records.  See ``session.py`` for the quickstart and
``python -m repro.compiler.cli --help`` for the command line.

Exports resolve lazily: ``repro.core.tuner`` imports the oracle/report
submodules directly, so an eager ``from .session import Session`` here
would close an import cycle.
"""
import importlib

_EXPORTS = {
    "Oracle": "repro.compiler.oracle",
    "AnalyticalOracle": "repro.compiler.oracle",
    "SettingsOracle": "repro.compiler.oracle",
    "CompileOracle": "repro.compiler.oracle",
    "decode_config": "repro.compiler.oracle",
    "Executor": "repro.compiler.executor",
    "SerialExecutor": "repro.compiler.executor",
    "SubprocessExecutor": "repro.compiler.executor",
    "WorkerSpec": "repro.compiler.executor",
    "MeasureResult": "repro.compiler.executor",
    "RecordLog": "repro.compiler.records",
    "TuneReport": "repro.compiler.report",
    "Tracker": "repro.compiler.report",
    "TuningTask": "repro.compiler.task",
    "Session": "repro.compiler.session",
    "SessionReport": "repro.compiler.session",
    "SurrogateStore": "repro.compiler.surrogate_store",
    "SurrogateSchemaError": "repro.compiler.surrogate_store",
    "RecordingGBT": "repro.compiler.surrogate_store",
    "NetworkTask": "repro.compiler.zoo",
    "get_network": "repro.compiler.zoo",
    "network_names": "repro.compiler.zoo",
}
__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module 'repro.compiler' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
