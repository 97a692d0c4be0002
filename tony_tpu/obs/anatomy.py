"""Step anatomy: where one training/decode step's time actually goes.

The report every ROADMAP speed claim needs: per captured step, a budget —
**compute / exposed-collective / host-blocked / input-wait** — whose rows
sum to the measured step time, plus per-collective achieved bandwidth and
the compute/collective overlap fraction. Inputs are exactly what the
coordinated capture (obs/profile.py) already landed under
``<app_dir>/profile/``:

- the per-process **manifest** (host step boundaries + per-step input
  wait, measured at the ``maybe_capture`` seam);
- the **device trace** jax.profiler wrote (the ``*.trace.json.gz`` Chrome
  trace next to the xplane proto — stdlib-parseable): XLA op events carry
  the HLO op names, and the ``anatomy.step`` annotation spans bracket each
  captured step on the timeline, so device activity aligns to steps
  without any cross-clock arithmetic;
- the **compile ledger**'s AOT entries (obs/compiles.py), whose extracted
  collective rows (obs/comms.py) carry bytes + replica groups — paired
  with measured event time BY OP NAME to yield achieved GB/s.

Attribution rule (one rule, stated once): within a step window, device
activity is the wall-clock union of XLA op intervals; the part of
collective time not overlapped by any compute op is *exposed*; compute is
the union of non-collective op wall time; input-wait is the host fetch
the seam recorded; host-blocked is the non-negative residual — so the
four rows sum to the measured step time by construction, and the
``device_trace`` flag says whether compute/exposed are measured or the
capture yielded no device events (everything then lands in host-blocked).

Stdlib-only: the report builds in deviceless CLI processes.
"""

from __future__ import annotations

import gzip
import json
import os
from typing import Any

from tony_tpu.obs import comms
from tony_tpu.obs import profile as profile_mod

# wrapper/runtime event names are never XLA ops: "ThunkExecutor::Execute",
# "TfrtCpuExecutable::ExecuteHelper", python tracer events ("$builtins ...")
_PY_PREFIX = "$"


# --- interval algebra ---------------------------------------------------------


def _merge(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted union of half-open intervals."""
    out: list[tuple[float, float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _total(merged: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in merged)


def _clip(merged: list[tuple[float, float]],
          window: tuple[float, float]) -> list[tuple[float, float]]:
    ws, we = window
    return [(max(s, ws), min(e, we)) for s, e in merged
            if min(e, we) > max(s, ws)]


def _subtract(a: list[tuple[float, float]],
              b: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """a minus b, both merged; the exposed-collective computation."""
    out: list[tuple[float, float]] = []
    bi = 0
    for s, e in a:
        cur = s
        while bi < len(b) and b[bi][1] <= cur:
            bi += 1
        j = bi
        while j < len(b) and b[j][0] < e:
            bs, be = b[j]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            j += 1
        if cur < e:
            out.append((cur, e))
    return out


# --- device-trace parsing -----------------------------------------------------


def _is_collective_event(name: str, known: frozenset = frozenset()) -> bool:
    """A trace event is a collective when the compile ledger says so —
    ``known`` holds the op NAMES of the compiled program's collective rows
    (obs/comms.py classifies by opcode; XLA names an op after whatever
    lowered it, e.g. ``psum_invariant.3`` for an ``all-reduce``) — or, for
    captures without a ledger, when its name is a collective opcode."""
    if name in known or name.replace("-done", "-start") in known:
        return True
    base = name.split(".")[0]
    if base in comms.COLLECTIVE_KINDS:
        return True
    for suffix in ("-start", "-done"):
        if base.endswith(suffix) and base[: -len(suffix)] in comms.COLLECTIVE_KINDS:
            return True
    return False


def load_device_trace(
    run_dir: str, collective_names: frozenset = frozenset()
) -> dict[str, Any]:
    """Parse the ``*.trace.json[.gz]`` files of one profiler run dir into
    step windows + device-op intervals (seconds, trace timebase).

    Classification: an X event is a device op when it sits on a device
    plane (process name ``/device:...``) or an XLA runtime thread
    (``tf_...``) AND its name is an op name — not a python-tracer event
    (``$...``) and not a C++ wrapper (``Class::Method``). The
    ``anatomy.step`` annotation spans (host thread) become the step
    windows. ``collective_names``: see :func:`_is_collective_event`."""
    out: dict[str, Any] = {
        "found": False, "step_windows": [], "compute": [], "collective": [],
        "collective_events": [], "files": [],
    }
    if not run_dir or not os.path.isdir(run_dir):
        return out
    names = sorted(
        n for n in os.listdir(run_dir)
        if n.endswith(".trace.json.gz") or n.endswith(".trace.json")
    )
    for name in names:
        path = os.path.join(run_dir, name)
        try:
            if name.endswith(".gz"):
                with gzip.open(path, "rt", encoding="utf-8") as f:
                    data = json.load(f)
            else:
                with open(path, encoding="utf-8") as f:
                    data = json.load(f)
        except (OSError, ValueError):
            continue
        events = data.get("traceEvents") or []
        proc_names: dict[Any, str] = {}
        thread_names: dict[tuple, str] = {}
        for e in events:
            if e.get("ph") != "M":
                continue
            if e.get("name") == "process_name":
                proc_names[e.get("pid")] = str(
                    (e.get("args") or {}).get("name", "")
                )
            elif e.get("name") == "thread_name":
                thread_names[(e.get("pid"), e.get("tid"))] = str(
                    (e.get("args") or {}).get("name", "")
                )
        for e in events:
            if e.get("ph") != "X":
                continue
            ename = str(e.get("name", ""))
            ts = float(e.get("ts", 0.0)) / 1e6
            dur = float(e.get("dur", 0.0)) / 1e6
            if ename == profile_mod.STEP_ANNOTATION:
                out["step_windows"].append((ts, ts + dur))
                continue
            if not ename or ename.startswith(_PY_PREFIX) or "::" in ename:
                continue
            pname = proc_names.get(e.get("pid"), "")
            tname = thread_names.get((e.get("pid"), e.get("tid")), "")
            if not (pname.startswith("/device:") or tname.startswith("tf_")):
                continue
            iv = (ts, ts + dur)
            if _is_collective_event(ename, collective_names):
                out["collective"].append(iv)
                out["collective_events"].append(
                    {"name": ename, "ts": ts, "dur_s": dur}
                )
            else:
                out["compute"].append(iv)
        out["files"].append(name)
        out["found"] = True
    out["step_windows"].sort()
    return out


# --- the budget table ---------------------------------------------------------


def step_budget(manifest: dict[str, Any],
                trace_data: dict[str, Any]) -> dict[str, Any]:
    """Per-step budget rows for one process's capture (see the module
    docstring for the attribution rule)."""
    step_times = [float(x) for x in manifest.get("step_time_s", [])]
    waits = [float(x) for x in manifest.get("input_wait_s", [])]
    windows = list(trace_data.get("step_windows", []))
    compute_all = _merge(trace_data.get("compute", []))
    coll_all = _merge(trace_data.get("collective", []))
    device_trace = bool(trace_data.get("found")) and bool(
        compute_all or coll_all
    )
    rows: list[dict[str, Any]] = []
    tot = {"step_time_s": 0.0, "compute_s": 0.0, "exposed_collective_s": 0.0,
           "input_wait_s": 0.0, "host_blocked_s": 0.0, "collective_s": 0.0}
    # overlap accumulators EXCLUDE pure-comm steps (collective time with
    # zero compute in the window — a sync barrier, an init broadcast): such
    # a step has no compute to hide under, so its collective time is 100%
    # exposed by construction and would dilute overlap_frac — one barrier
    # step could mask a real overlap regression in the training steps
    ov_coll = ov_exposed = 0.0
    pure_comm_steps = 0
    for i, step_time in enumerate(step_times):
        wait = waits[i] if i < len(waits) else 0.0
        compute_s = exposed_s = coll_s = 0.0
        if device_trace and i < len(windows):
            w = windows[i]
            compute = _clip(compute_all, w)
            coll = _clip(coll_all, w)
            compute_s = _total(compute)
            coll_s = _total(coll)
            exposed_s = _total(_subtract(coll, compute))
        host = max(step_time - compute_s - exposed_s - wait, 0.0)
        pure_comm = coll_s > 0.0 and compute_s == 0.0
        row = {
            "step": i + 1,
            "step_time_s": round(step_time, 6),
            "compute_s": round(compute_s, 6),
            "exposed_collective_s": round(exposed_s, 6),
            "input_wait_s": round(wait, 6),
            "host_blocked_s": round(host, 6),
        }
        if pure_comm:
            row["pure_comm"] = True
            pure_comm_steps += 1
        else:
            ov_coll += coll_s
            ov_exposed += exposed_s
        rows.append(row)
        tot["step_time_s"] += step_time
        tot["compute_s"] += compute_s
        tot["exposed_collective_s"] += exposed_s
        tot["input_wait_s"] += wait
        tot["host_blocked_s"] += host
        tot["collective_s"] += coll_s
    n = max(len(rows), 1)
    out = {
        "steps": len(rows),
        "device_trace": device_trace,
        "table": rows,
        "totals": {k: round(v, 6) for k, v in tot.items()},
        "per_step_ms": {
            k: round(tot[k] / n * 1e3, 3)
            for k in ("step_time_s", "compute_s", "exposed_collective_s",
                      "input_wait_s", "host_blocked_s")
        },
    }
    if pure_comm_steps:
        out["pure_comm_steps"] = pure_comm_steps
    if ov_coll > 0:
        # fraction of collective time hidden under compute: the overlap
        # number `tony perf diff` judges higher-is-better. Pure-comm steps
        # are excluded (flagged per row) — they have nothing to overlap.
        out["overlap_frac"] = round(1.0 - ov_exposed / ov_coll, 4)
    return out


def collective_table(trace_data: dict[str, Any],
                     ledger_rows: list[dict[str, Any]] | None
                     ) -> list[dict[str, Any]]:
    """Per-collective rows: static bytes/replica-groups from the compile
    ledger (obs/comms.py) joined with measured device-trace time BY OP
    NAME; achieved bandwidth where both sides exist. Ledger-only rows
    (never executed in the window) and trace-only rows (no AOT entry —
    e.g. a lazily jitted fn) are kept, flagged by what they miss — the
    table never silently drops either side."""
    measured: dict[str, dict[str, float]] = {}
    for ev in trace_data.get("collective_events", []):
        m = measured.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
        m["count"] += 1
        m["total_s"] += ev["dur_s"]
    by_name: dict[str, dict[str, Any]] = {}
    for row in ledger_rows or []:
        by_name.setdefault(row["name"], {
            "name": row["name"], "kind": row["kind"],
            "bytes": int(row.get("bytes", 0)),
            "replica_groups": row.get("replica_groups", ""),
        })
    for name, m in measured.items():
        entry = by_name.setdefault(name, {
            "name": name, "kind": name.split(".")[0], "bytes": 0,
            "replica_groups": "",
        })
        entry["count"] = int(m["count"])
        entry["total_s"] = round(m["total_s"], 6)
        entry["mean_us"] = round(m["total_s"] / m["count"] * 1e6, 3)
        if entry["bytes"] and m["total_s"] > 0:
            # 4 significant figures, not fixed decimals: CPU-test and DCN
            # bandwidths live orders of magnitude below ICI ones
            entry["achieved_gbps"] = float(
                f"{entry['bytes'] * m['count'] / m['total_s'] / 1e9:.4g}"
            )
    rows = sorted(
        by_name.values(),
        key=lambda r: (-r.get("total_s", 0.0), -r.get("bytes", 0), r["name"]),
    )
    return rows


def ledger_collectives(ledger_payload: dict[str, Any] | None
                       ) -> list[dict[str, Any]]:
    """Flatten one process's compile-ledger snapshot (obs/compiles.py) to
    its AOT entries' collective rows, tagged with the entry fn."""
    rows: list[dict[str, Any]] = []
    for entry in (ledger_payload or {}).get("entries", []) or []:
        for c in entry.get("collectives") or []:
            rows.append({**c, "fn": entry.get("fn", "")})
    return rows


def proc_report(manifest: dict[str, Any],
                ledger_rows: list[dict[str, Any]] | None = None
                ) -> dict[str, Any]:
    """The full anatomy of ONE process's capture."""
    trace_data = load_device_trace(
        manifest.get("artifact", ""),
        frozenset(r["name"] for r in ledger_rows or ()),
    )
    budget = step_budget(manifest, trace_data)
    colls = collective_table(trace_data, ledger_rows)
    return {
        "profile_id": manifest.get("profile_id", ""),
        "proc": manifest.get("proc", ""),
        "artifact": manifest.get("artifact", ""),
        **budget,
        "collectives": colls,
    }


def build_anatomy(app_dir: str, profile_id: str = "") -> dict[str, Any]:
    """``tony profile report``: every process's budget table + collective
    rows for one capture (newest when unspecified), plus the cross-host
    critical path — per aligned step, the process whose step took longest
    is the one gating the gang (pipeline stage or decode host alike)."""
    from tony_tpu.obs.compiles import read_app_ledgers

    manifests = profile_mod.read_manifests(app_dir, profile_id)
    out: dict[str, Any] = {"profile_id": profile_id, "procs": {}}
    if not manifests:
        return out
    ledgers = read_app_ledgers(app_dir)
    for proc, manifest in sorted(manifests.items()):
        out["profile_id"] = manifest.get("profile_id", profile_id)
        out["procs"][proc] = proc_report(
            manifest, ledger_collectives(ledgers.get(proc))
        )
    # critical path: per step index, the slowest process owns the fleet's
    # wall clock for that step
    by_step: list[dict[str, Any]] = []
    n_steps = max(
        (r["steps"] for r in out["procs"].values()), default=0
    )
    dominated: dict[str, int] = {}
    for i in range(n_steps):
        best_proc, best_t = "", -1.0
        for proc, rep in out["procs"].items():
            if i < len(rep["table"]):
                t = rep["table"][i]["step_time_s"]
                if t > best_t:
                    best_proc, best_t = proc, t
        if best_proc:
            by_step.append({
                "step": i + 1, "proc": best_proc,
                "step_time_s": round(best_t, 6),
            })
            dominated[best_proc] = dominated.get(best_proc, 0) + 1
    if by_step:
        out["critical_path"] = {
            "proc": max(dominated, key=dominated.get),
            "dominated_steps": dominated,
            "by_step": by_step,
        }
    return out


__all__ = [
    "build_anatomy", "collective_table", "ledger_collectives",
    "load_device_trace", "proc_report", "step_budget",
]
