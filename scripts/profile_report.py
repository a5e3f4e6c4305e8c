"""Render a node's serving-cycle profile as a terminal report.

Fetches /v1/debug/profile and /v1/debug/kernels from a running node's
HTTP gateway and prints the operator-facing digest: the per-phase
decomposition of the serial serving cycle (boot-cumulative shares plus
the last-minute window), per-call-site engine-lock wait, and the kernel
cost/dispatch table. This is the same data the `profile_shift` anomaly
detector reads from the history ring — the report exists so a human can
see WHERE the cycle's time went before (or after) the detector trips
(see docs/OPERATIONS.md "Performance triage").

Usage:
    python scripts/profile_report.py [host:port]   # default 127.0.0.1:80
    make profile-report [ADDR=host:port]

Rendering is a pure function over the two endpoint bodies
(render_report), so tests exercise it offline; only main() touches the
network. Exit status: 0 rendered, 1 on fetch/shape failure.
"""

import json
import sys
import urllib.request


def _fmt_ns(ns):
    if ns is None:
        return "n/a"
    ns = float(ns)
    if ns >= 1e9:
        return f"{ns / 1e9:.2f}s"
    if ns >= 1e6:
        return f"{ns / 1e6:.2f}ms"
    if ns >= 1e3:
        return f"{ns / 1e3:.1f}us"
    return f"{ns:.0f}ns"


def _bar(fraction, width=28):
    fraction = min(max(float(fraction or 0.0), 0.0), 1.0)
    filled = int(round(fraction * width))
    return "[" + "#" * filled + "." * (width - filled) + "]"


def _phase_block(lines, title, phases_dec):
    lines.append(title)
    lines.append("-" * 58)
    for p, d in phases_dec.items():
        share = d.get("share", 0.0)
        n = d.get("count", d.get("n", 0))
        total_ns = d.get("total_ns")
        total_s = d.get("total_s", (total_ns or 0) / 1e9)
        caveat = "  (pipeline residency)" if p == "queue_wait" else ""
        lines.append(f"{p:<11} {_bar(share)} {share:>6.1%}  "
                     f"{total_s:>9.3f}s / {n} windows{caveat}")


def render_report(profile_body, kernels_body=None):
    """Pure renderer: endpoint bodies in, report text out."""
    lines = []
    lines.append("serving-cycle profile")
    lines.append("=" * 58)
    if not profile_body.get("enabled", True):
        lines.append("profiler DISABLED (GUBER_PROFILE=0) — counters "
                     "frozen at the values below")
    dec = profile_body.get("decomposition") or {}
    if not any((d.get("count") or 0) for d in dec.values()):
        lines.append("no serving cycles observed yet")
        return "\n".join(lines) + "\n"

    _phase_block(lines, "cycle decomposition (boot-cumulative, share of "
                        "serial cycle)", dec)
    lines.append("")

    recent = profile_body.get("recent") or {}
    rp = recent.get("phases") or {}
    if any((d.get("n") or 0) for d in rp.values()):
        win = recent.get("window_s")
        _phase_block(
            lines,
            f"last {win:.0f}s" if win else "since boot (ring still filling)",
            rp)
        lines.append("")

    for block, title, unit in (("lock_sites", "wait", "waits"),
                               ("lock_hold_sites", "hold", "holds")):
        sites = profile_body.get(block) or {}
        lines.append(f"engine-lock {title} by call site")
        lines.append("-" * 58)
        if sites:
            for s, h in sorted(sites.items(),
                               key=lambda kv: -(kv[1].get("total_ns") or 0)):
                lines.append(f"{s:<24} {h.get('n'):>9} {unit}  "
                             f"p50 {_fmt_ns(h.get('p50_ns')):>9}  "
                             f"p99 {_fmt_ns(h.get('p99_ns')):>9}  "
                             f"total {_fmt_ns(h.get('total_ns'))}")
        else:
            lines.append("(none recorded)")
        lines.append("")

    cap = profile_body.get("capture") or {}
    lines.append(f"deep captures  {cap.get('count', 0)} taken "
                 f"(min {cap.get('min_interval_s')}s apart; "
                 "?capture=1 to trigger)")
    if cap.get("last_path"):
        lines.append(f"  last: {cap['last_path']} ({cap.get('last_mode')})")

    if kernels_body is not None:
        lines.append("")
        lines.append("kernel dispatch & cost")
        lines.append("-" * 58)
        kernels = kernels_body.get("kernels") or {}
        if not kernels:
            lines.append("(no kernels dispatched yet)")
        for name, rec in kernels.items():
            hist = rec.get("dispatch_ns") or {}
            cost = rec.get("cost") or {}
            cost_txt = (f"flops {cost['flops']:.3g} "
                        f"bytes {cost['bytes_accessed']:.3g}"
                        if "flops" in cost
                        else cost.get("error") or cost.get("cost_error")
                        or "cost n/a")
            lines.append(f"{name:<22} {rec.get('windows'):>9} windows  "
                         f"dispatch p99 {_fmt_ns(hist.get('p99_ns')):>9}  "
                         f"{cost_txt}")
    return "\n".join(lines) + "\n"


def _fetch(addr, path, timeout=5.0):
    return json.loads(urllib.request.urlopen(
        f"http://{addr}{path}", timeout=timeout).read())


def main(argv):
    addr = argv[1] if len(argv) > 1 else "127.0.0.1:80"
    try:
        prof = _fetch(addr, "/v1/debug/profile")
        # the kernels body may pay first-call cost compiles; give it room
        kern = _fetch(addr, "/v1/debug/kernels", timeout=30.0)
    except Exception as e:  # noqa: BLE001 — operator tool, report and exit
        print(f"profile_report: fetch from {addr} failed: {e}",
              file=sys.stderr)
        return 1
    try:
        sys.stdout.write(render_report(prof, kern))
    except Exception as e:  # noqa: BLE001
        print(f"profile_report: unexpected endpoint shape: {e}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
