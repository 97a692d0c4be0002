"""Compile ledger: an always-on journal of every XLA compile in a process.

analysis/sanitize.py proved the shape: a ``jax.monitoring`` duration
listener counting ``backend_compile`` events is cheap enough to leave
installed forever. This module grows that counter into a *ledger* — every
backend compile lands as an entry with its duration, wall time, and the
function name the instrumented call site attributed (the monitoring event
itself is anonymous, so attribution rides a thread-local :meth:`label`
scope the compile-ahead thread / engine wrap around their compiles).

AOT-compiled entry points (the train step via fit()'s compile-ahead, the
decode step via the serve engine, bench.py's measured sections) call
:func:`record_aot` with the compiled executable, which additionally
records ``memory_analysis()`` (temp/argument/output/code bytes — the
measured memory plan) and ``cost_analysis()`` FLOPs — the numbers bench
MFU and the gqa_capacity slot budget are derived from, replacing hand
formulas.

Each process snapshots its ledger to ``<app_dir>/compiles/<proc>.json``
at fit()/engine shutdown (and inside the OOM forensics dump);
``tony compiles <app_id>`` merges them into one report.

The sanitize watchdog's ``compile_count()`` now reads this ledger's
counter, so one listener serves both the budget check and the journal.

jax is imported lazily: only :func:`get_ledger` needs it, and the CLI
read path (:func:`read_app_ledgers`) must work in processes without a
device.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque
from typing import Any

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def aot_analysis(compiled) -> dict[str, Any]:
    """memory_analysis + cost_analysis of a compiled executable as plain
    numbers; parts a backend doesn't expose are simply absent."""
    out: dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            out.update(
                temp_bytes=int(ma.temp_size_in_bytes),
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                generated_code_bytes=int(ma.generated_code_size_in_bytes),
            )
    except (NotImplementedError, RuntimeError):  # backend has no such analysis
        pass
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if ca:
            for key, name in (("flops", "flops"),
                              ("bytes accessed", "bytes_accessed")):
                if key in ca:
                    out[name] = float(ca[key])
    except (NotImplementedError, RuntimeError):
        pass
    return out


class CompileLedger:
    """Bounded in-memory journal + monotonic compile counter."""

    def __init__(self, max_entries: int = 2048):
        self._entries: deque = deque(maxlen=max(int(max_entries), 64))
        self._lock = threading.Lock()
        self._tls = threading.local()
        self.backend_compiles = 0  # monotonic, never trimmed with the deque

    # --- attribution ----------------------------------------------------------

    @contextlib.contextmanager
    def label(self, name: str):
        """Attribute backend-compile events fired on THIS thread inside the
        block to ``name`` (jax's monitoring event carries no function name;
        the call site that triggers the compile knows it)."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append(name)
        try:
            yield
        finally:
            stack.pop()

    def _current_label(self) -> str:
        stack = getattr(self._tls, "stack", None)
        return stack[-1] if stack else ""

    # --- recording ------------------------------------------------------------

    def on_event(self, event: str, duration: float) -> None:
        if event != BACKEND_COMPILE_EVENT:
            return
        entry = {
            "ts": time.time(),
            "kind": "backend",
            "fn": self._current_label(),
            "dur_s": round(float(duration), 4),
        }
        with self._lock:
            self.backend_compiles += 1
            self._entries.append(entry)

    def record_aot(self, fn: str, compiled, dur_s: float = 0.0) -> dict:
        """Journal an ahead-of-time compile with its measured memory plan,
        FLOPs, and the collective set extracted from its optimized HLO
        (obs/comms.py: op kind, payload bytes, replica groups — what the
        step-anatomy report pairs measured device-trace time against);
        returns the entry (bench reuses the numbers)."""
        entry = {
            "ts": time.time(),
            "kind": "aot",
            "fn": fn,
            "dur_s": round(float(dur_s), 4),
            **aot_analysis(compiled),
        }
        from tony_tpu.obs.comms import extract_collectives

        colls = extract_collectives(compiled)  # [] when there is no HLO text
        if colls:
            entry["collectives"] = colls
        with self._lock:
            self._entries.append(entry)
        return entry

    # --- reading --------------------------------------------------------------

    def entries(self, kind: str = "") -> list[dict]:
        with self._lock:
            snap = list(self._entries)
        if kind:
            snap = [e for e in snap if e.get("kind") == kind]
        return snap

    def to_dict(self) -> dict:
        return {
            "backend_compiles": self.backend_compiles,
            "entries": self.entries(),
        }


# --- process-global ledger ---------------------------------------------------

_ledger: CompileLedger | None = None
_install_lock = threading.Lock()


def get_ledger() -> CompileLedger:
    """The process ledger; first call installs the (permanent, cheap)
    monitoring listener — jax.monitoring has no per-listener removal, so
    it registers exactly once and watchdogs compare counter snapshots."""
    global _ledger
    if _ledger is not None:
        return _ledger
    with _install_lock:
        if _ledger is None:
            ledger = CompileLedger()
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                lambda event, duration, **_kw: ledger.on_event(event, duration)
            )
            _ledger = ledger
    return _ledger


def snapshot_to_app_dir(proc: str = "",
                        ledger: CompileLedger | None = None) -> str:
    """Atomically journal the ledger under the job's app dir when running
    inside a tony-tpu job (TONY_APP_DIR); returns the path ('' outside).
    The ledger is process-scoped, so the snapshot carries the bare proc
    name — a train-then-serve process overwrites its own file with a
    superset, never another component's."""
    app_dir = os.environ.get("TONY_APP_DIR", "")
    if not app_dir:
        return ""
    from tony_tpu.obs.trace import default_proc_name, sanitize_proc

    proc = sanitize_proc(proc) if proc else default_proc_name()
    led = ledger if ledger is not None else _ledger
    if led is None:
        return ""
    path = os.path.join(app_dir, "compiles", f"{proc}.json")
    payload = {"proc": proc, **led.to_dict()}
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp", "w", encoding="utf-8") as f:
            json.dump(payload, f, default=str)
        os.replace(path + ".tmp", path)
    except OSError:
        return ""
    return path


def read_app_ledgers(app_dir: str) -> dict[str, dict]:
    """Every process's ledger snapshot under an app dir (``tony compiles``
    and the portal read path); proc name -> payload."""
    cdir = os.path.join(app_dir, "compiles")
    out: dict[str, dict] = {}
    if not os.path.isdir(cdir):
        return out
    for name in sorted(os.listdir(cdir)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(cdir, name), encoding="utf-8") as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(payload, dict):
            out[payload.get("proc") or name[:-5]] = payload
    return out


def summarize(ledgers: dict[str, dict]) -> dict:
    """The ``tony compiles`` report: per-process counts/durations plus the
    AOT entries with their measured memory plans."""
    procs = {}
    for proc, payload in sorted(ledgers.items()):
        entries = payload.get("entries", []) or []
        backend = [e for e in entries if e.get("kind") == "backend"]
        aot = [e for e in entries if e.get("kind") == "aot"]
        procs[proc] = {
            "backend_compiles": payload.get("backend_compiles", len(backend)),
            "compile_time_s": round(
                sum(float(e.get("dur_s", 0.0)) for e in backend), 3
            ),
            "aot_entry_points": aot,
            "entries": entries,
        }
    return {
        "processes": procs,
        "total_backend_compiles": sum(
            p["backend_compiles"] for p in procs.values()
        ),
    }


__all__ = [
    "BACKEND_COMPILE_EVENT", "CompileLedger", "aot_analysis", "get_ledger",
    "read_app_ledgers", "snapshot_to_app_dir", "summarize",
]
