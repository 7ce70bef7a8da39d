"""Per-request latency waterfalls from flight-recorder event JSONL.

The serving path stamps every event with causal trace/span ids
(obs/trace.py) and emits per-request stage spans (queued → prefill →
decode under a ``request`` envelope). This tool is the triage half: it
reconstructs each request's waterfall, prints the critical path per
round, and — the load-bearing part — **checks** the decomposition: a
request's stage walls (prefill + decode) must sum to its reported
service wall within tolerance. A waterfall that doesn't add up is a
telemetry bug, and this tool treats it as one (exit 1), so the
decomposition stays checked, not decorative.

A disaggregated fleet dump (reason="prefill" RouteEvents + "ship"
SwapEvents, fleet/router.py + fleet/handoff.py) additionally annotates
each handed-off request's waterfall head with the handoff path —
``prefill@r0 -> decode@r2 (N blocks shipped)`` — so the cross-replica
KV handoff is readable straight off the view.

``--xplane`` reads a jax profile instead (the ``profile`` op of
``advspec serve``, or any ``jax.profiler`` trace of the serving
process): the host's ``advspec.*`` phases (``obs.phase``) sit in it on
the device's clock, so every second the device was idle is attributed
to the innermost phase that was open on the host at that moment.

Usage:
    python tools/trace_view.py events.jsonl               # waterfalls + check
    python tools/trace_view.py events.jsonl --trace ID    # one round only
    python tools/trace_view.py events.jsonl --json        # machine-readable
    python tools/trace_view.py --xplane t.xplane.pb       # device idle by phase

Exit codes: 0 = every request's decomposition checks out (``--xplane``:
a device plane was read); 1 = a sum violation or schema error (no
device plane); 2 = unreadable input.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from perfbench.spans import (  # noqa: E402
    IDLE_PARTS,
    NO_SPAN as NO_PHASE,
    PREFIX as PHASE_PREFIX,
    SHARES,
    idle_by_phase,
    share,
)
from tools.obs_dump import load_events  # noqa: E402

# |request_wall - (prefill + decode)| must stay within
# max(ABS_TOL, REL_TOL * request_wall). The scheduler computes the
# envelope as exactly prefill + decode, and the mock's synthetic
# seconds are exact binary fractions — the tolerance only absorbs the
# dump-time 6-decimal rounding of each float.
ABS_TOL = 1e-5
REL_TOL = 0.01

# Stage render order in a waterfall row.
STAGES = ("queued", "prefill", "decode")


def collect_requests(events: list[dict]) -> dict[str, dict]:
    """Group span events by span_id into per-request records:
    ``{span_id: {trace_id, req_id, begin_seq, stages: {name: wall},
    ended, extra}}``. A re-emitted stage (a requeued request prefilling
    twice) keeps the LAST end wall — the one the request actually paid
    on its surviving attempt. Fleet RouteEvents stamped with the span
    collect under ``route`` (in seq order), so a failover hop — the
    request leaving a dead replica for a survivor — is visible right
    in the waterfall head instead of only in the raw dump."""
    out: dict[str, dict] = {}
    routes: dict[str, list[dict]] = {}
    ships: dict[str, int] = {}
    # Armed recordings (ADVSPEC_OBS_ARRIVALS) stamp the queue-edge
    # RequestEvent with the monotonic arrival offset; carry it onto the
    # span record by req_id so the waterfall head shows WHEN each
    # request entered, not just how long its stages took.
    arrivals: dict[int, float] = {}
    for e in events:
        if (
            e["type"] == "request"
            and e.get("state") == "queued"
            and e.get("arrival_s", 0) > 0
        ):
            arrivals[e["req_id"]] = e["arrival_s"]
        if (
            e["type"] == "swap"
            and e["op"] == "ship"
            and e.get("span_id")
        ):
            # Handoff publications stamped with the request's span: the
            # block count feeds the waterfall's handoff annotation.
            ships[e["span_id"]] = ships.get(e["span_id"], 0) + e["blocks"]
            continue
        if e["type"] == "route" and e.get("span_id"):
            routes.setdefault(e["span_id"], []).append(
                {
                    "replica": e["replica"],
                    "hop": e["hop"],
                    "reason": e["reason"],
                    "seq": e["seq"],
                }
            )
            continue
        if e["type"] != "span" or not e["span_id"]:
            continue
        rec = out.setdefault(
            e["span_id"],
            {
                "trace_id": e["trace_id"],
                "req_id": e.get("req_id", -1),
                "begin_seq": e["seq"],
                "stages": {},
                "request_wall": None,
                "end_seq": None,
                "cancelled": False,
                "route": [],
            },
        )
        rec["begin_seq"] = min(rec["begin_seq"], e["seq"])
        if e["phase"] == "begin":
            continue
        # ``cancelled`` closes a request envelope mid-decode (streaming
        # early convergence) exactly like ``end`` does — it carries the
        # service wall so far, so the decomposition check below covers
        # cancelled requests too (their truncated span set still sums).
        if e["name"] == "request":
            rec["request_wall"] = e["wall_s"]
            rec["end_seq"] = e["seq"]
            rec["cancelled"] = e["phase"] == "cancelled"
        elif e["name"] in STAGES:
            rec["stages"][e["name"]] = e["wall_s"]
    for span_id, hops in routes.items():
        if span_id in out:
            out[span_id]["route"] = sorted(hops, key=lambda h: h["seq"])
    for span_id, blocks in ships.items():
        if span_id in out:
            out[span_id]["shipped_blocks"] = blocks
    for rec in out.values():
        if rec["req_id"] in arrivals:
            rec["arrival_s"] = arrivals[rec["req_id"]]
    return out


def check_decomposition(requests: dict[str, dict]) -> list[str]:
    """The contract: for every request whose envelope closed with both
    device stages present, prefill + decode == request wall within
    tolerance (queued time is WAIT, deliberately outside the service
    envelope). Returns human-readable violations (empty = all good)."""
    problems: list[str] = []
    for span_id, rec in sorted(requests.items()):
        wall = rec["request_wall"]
        stages = rec["stages"]
        if wall is None or "prefill" not in stages or "decode" not in stages:
            continue  # evicted/timeout mid-flight: nothing to check
        total = stages["prefill"] + stages["decode"]
        if abs(wall - total) > max(ABS_TOL, REL_TOL * wall):
            problems.append(
                f"{span_id}: stage walls sum to {total:.6f}s but the "
                f"request reported {wall:.6f}s service"
            )
    return problems


def render_waterfall(
    requests: dict[str, dict], width: int = 32
) -> str:
    """Per-request bars, one row per stage, scaled to the slowest
    request's service wall — the 'where did this opponent's round go'
    view."""
    if not requests:
        return "(no request spans)"
    scale = max(
        (
            sum(r["stages"].values())
            for r in requests.values()
            if r["stages"]
        ),
        default=0.0,
    )
    rows: list[str] = []
    for span_id, rec in sorted(
        requests.items(), key=lambda kv: kv[1]["begin_seq"]
    ):
        wall = rec["request_wall"]
        head = f"{span_id}  (req {rec['req_id']}"
        if rec.get("arrival_s"):
            head += f", @{rec['arrival_s']:.3f}s"
        head += (
            f", service {wall:.4f}s"
            + (", CANCELLED" if rec.get("cancelled") else "")
            + ")"
            if wall is not None
            else ", open)"
        )
        hops = rec.get("route") or []
        # A disagg handoff stamps an extra reason="prefill" route at
        # the prefill replica before the ordinary decode-side route:
        # render it as its own annotation ("prefill@r0 -> decode@r2
        # (N blocks shipped)") and keep the via-chain to the replicas
        # that actually served the request.
        pre_hops = [h for h in hops if h["reason"] == "prefill"]
        hops = [h for h in hops if h["reason"] != "prefill"]
        if hops:
            # The replica path: "via r0" normally; a failover shows the
            # whole chain ("via r0 -> r1 (failover)") so a replica loss
            # is readable straight off the waterfall.
            path = " -> ".join(h["replica"] for h in hops)
            head += f"  via {path}"
            if hops[-1]["hop"] > 0:
                head += f" ({hops[-1]['reason']})"
        if pre_hops:
            dec = hops[0]["replica"] if hops else "?"
            head += (
                f"  handoff prefill@{pre_hops[0]['replica']} -> "
                f"decode@{dec}"
            )
            if rec.get("shipped_blocks"):
                head += f" ({rec['shipped_blocks']} blocks shipped)"
        rows.append(head)
        offset = 0.0
        for name in STAGES:
            if name not in rec["stages"]:
                continue
            w = rec["stages"][name]
            lead = round(offset / scale * width) if scale else 0
            fill = max(round(w / scale * width), 1) if scale else 0
            fill = min(fill, width - lead)
            bar = " " * lead + "█" * fill
            rows.append(f"  {name:<8} |{bar:<{width}}| {w:.4f}s")
            if name != "queued":  # wait time doesn't advance service
                offset += w
        rows.append("")
    return "\n".join(rows).rstrip()


def critical_path(requests: dict[str, dict]) -> str:
    """Per-trace summary: request count, total service, and the
    slowest request with its dominant stage — the first thing to read
    when an SLO capture lands."""
    traces: dict[str, list[tuple[str, dict]]] = {}
    for span_id, rec in requests.items():
        traces.setdefault(rec["trace_id"], []).append((span_id, rec))
    lines: list[str] = []
    for trace_id in sorted(traces):
        recs = traces[trace_id]
        closed = [
            (sid, r) for sid, r in recs if r["request_wall"] is not None
        ]
        lines.append(
            f"trace {trace_id or '(unstamped)'}: {len(recs)} request(s), "
            f"{len(closed)} closed"
        )
        if not closed:
            continue
        sid, worst = max(closed, key=lambda kv: kv[1]["request_wall"])
        stages = worst["stages"]
        dom = max(stages, key=stages.get) if stages else "?"
        lines.append(
            f"  critical path: {sid} at {worst['request_wall']:.4f}s "
            f"(dominant stage: {dom}"
            + (f" {stages[dom]:.4f}s)" if stages else ")")
        )
        for name in STAGES:
            total = sum(r["stages"].get(name, 0.0) for _, r in closed)
            lines.append(f"  total {name:<8} {total:.4f}s")
    return "\n".join(lines) if lines else "(no traced requests)"


def membership_changes(events: list[dict]) -> str:
    """Autoscaler transitions in seq order — fleet membership changing
    UNDER the waterfall explains a latency cliff (a request queued
    while the fleet was one replica short) without leaving the view."""
    scales = [e for e in events if e["type"] == "scale"]
    if not scales:
        return ""
    lines = ["membership changes:"]
    for s in sorted(scales, key=lambda e: e["seq"]):
        lines.append(
            f"  seq {s['seq']:>6} {s['op']:<12} "
            + " ".join(
                n
                for n in (
                    s["replica"],
                    s["direction"] and f"dir={s['direction']}",
                    s["reason"],
                    f"desired={s['desired']}",
                    f"alive={s['alive']}",
                    f"backlog={s['backlog_tokens']}",
                )
                if n
            )
        )
    return "\n".join(lines)


# -- device idle time by host phase (--xplane) ------------------------------
# The table and its four shares are perfbench/spans.py's.


def _union(intervals: list[tuple]) -> list[tuple]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def xplane_report(path: str) -> dict | None:
    """Read a ``.xplane.pb``: the first device plane's busy intervals
    (its ``XLA Ops`` line) and every ``advspec.*`` host event. None when
    the file holds no device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device = re.compile(r"^/device:(TPU|GPU):\d+$")
    busy = None
    phases: list[tuple] = []
    for plane in sorted(data.planes, key=lambda pl: pl.name):
        if device.match(plane.name) and busy is None:
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops") or next(iter(lines.values()), None)
            if line is not None:
                busy = _union(
                    [
                        (e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                        if e.duration_ns > 0
                    ]
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PHASE_PREFIX) and e.duration_ns > 0:
                        phases.append(
                            (
                                e.name[len(PHASE_PREFIX):],
                                e.start_ns,
                                e.start_ns + e.duration_ns,
                            )
                        )
    if not busy:
        return None
    window_s = (busy[-1][1] - busy[0][0]) / 1e9
    busy_s = sum(b - a for a, b in busy) / 1e9
    wall: dict[str, float] = {}
    count: dict[str, int] = {}
    for name, a, b in phases:
        wall[name] = wall.get(name, 0.0) + (b - a) / 1e9
        count[name] = count.get(name, 0) + 1
    by_phase = idle_by_phase(busy, phases)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_s": window_s - busy_s,
        "idle_by_phase_s": by_phase,
        "idle_shares": {
            name: share(by_phase, rx, window_s) for name, rx in SHARES.items()
        },
        "phase_wall_s": wall,
        "phase_count": count,
    }


def render_xplane(rep: dict) -> str:
    idle_s = rep["idle_s"]
    lines = [
        f"device window {rep['window_s']:.3f} s, busy {rep['busy_s']:.3f} s, "
        f"idle {idle_s:.3f} s "
        f"({100.0 * idle_s / rep['window_s']:.1f}%)",
        "",
        f"{'phase':<24}{'idle_s':>8}{'of idle':>9}"
        + "".join(f"{part:>9}" for part in IDLE_PARTS)
        + f"{'wall_s':>9}{'uses':>6}",
    ]
    by = rep["idle_by_phase_s"]
    for name in sorted(by, key=lambda n: -by[n]["idle_s"]):
        row = by[name]
        share = 100.0 * row["idle_s"] / idle_s if idle_s > 0 else 0.0
        wall = rep["phase_wall_s"].get(name)
        lines.append(
            f"{name:<24}{row['idle_s']:>8.3f}{share:>8.1f}%"
            + "".join(f"{row[part]:>9.3f}" for part in IDLE_PARTS)
            + (f"{wall:>9.3f}" if wall is not None else f"{'':>9}")
            + f"{rep['phase_count'].get(name, 0):>6}"
        )
    lines.append("")
    lines += [f"{name:<24}{v:>8.2f}%" for name, v in rep["idle_shares"].items()]
    return "\n".join(lines)


def main_xplane(path: str, as_json: bool) -> int:
    try:
        rep = xplane_report(path)
    except OSError as e:
        print(f"trace_view: {e}", file=sys.stderr)
        return 2
    if rep is None:
        print(
            "trace_view: no device plane in this profile (it was not "
            "taken by the process that holds the chip)",
            file=sys.stderr,
        )
        return 1
    print(json.dumps(rep, indent=2, sort_keys=True) if as_json
          else render_xplane(rep))
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "path", nargs="?", help="events JSONL file to render"
    )
    ap.add_argument(
        "--xplane",
        metavar="FILE",
        help="a jax profile (.xplane.pb): device idle seconds by the "
        "advspec.* phase open on the host",
    )
    ap.add_argument(
        "--trace", help="restrict to one trace id (one debate round)"
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="machine-readable per-request records + check verdicts",
    )
    ap.add_argument(
        "--no-check",
        action="store_true",
        help="render only; skip the stage-sum consistency check",
    )
    args = ap.parse_args(argv)
    if args.xplane:
        return main_xplane(args.xplane, args.json)
    if not args.path:
        ap.error("an events JSONL file, or --xplane FILE")
    try:
        events, errors = load_events(args.path)
    except OSError as e:
        print(f"trace_view: {e}", file=sys.stderr)
        return 2
    for err in errors:
        print(f"trace_view: {err}", file=sys.stderr)
    if args.trace:
        events = [e for e in events if e.get("trace_id") == args.trace]
    requests = collect_requests(events)
    problems = [] if args.no_check else check_decomposition(requests)
    if args.json:
        print(
            json.dumps(
                {
                    "requests": requests,
                    "check_problems": problems,
                },
                indent=2,
                sort_keys=True,
            )
        )
    else:
        print(render_waterfall(requests))
        print()
        print(critical_path(requests))
        scales = membership_changes(events)
        if scales:
            print()
            print(scales)
    for p in problems:
        print(f"trace_view: DECOMPOSITION VIOLATION: {p}", file=sys.stderr)
    if problems or errors:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
