"""Run the scenario atlas and write the round's SCEN_r<NN>.json verdict.

Each scenario boots its own in-process LocalCluster (1-2 nodes per the
spec), paces the seeded schedule onto it, fires the spec's timeline
events, and records the SLO verdict the anomaly engine + envelope
render. The artifact is machine-readable, diffable across rounds, and
gated — exit status 1 when any scenario FAILs, so `make scenarios` is
red exactly when an operator would have been paged.

Usage:
    python scripts/scenario_report.py                  # short atlas
    python scripts/scenario_report.py --profile full   # 870s-scale drills
    python scripts/scenario_report.py --scenario bot-storm --scenario ...
    python scripts/scenario_report.py --autopilot both # off + on per shape
    python scripts/scenario_report.py --replay trace.json
    python scripts/scenario_report.py --list
    python scripts/scenario_report.py --out SCEN_r02.json

With --autopilot both, each shape runs twice on the same seed — static
knobs, then GUBER_AUTOPILOT-armed via the spec overlay — and the armed
run is keyed "<name>@autopilot".
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _next_round_path() -> str:
    rounds = []
    for p in glob.glob(os.path.join(REPO, "SCEN_r*.json")):
        m = re.match(r"SCEN_r(\d+)\.json$", os.path.basename(p))
        if m:
            rounds.append(int(m.group(1)))
    return os.path.join(REPO, f"SCEN_r{(max(rounds) + 1 if rounds else 1):02d}.json")


def main(argv=None) -> int:
    from gubernator_tpu.scenarios import (
        SCENARIO_NAMES,
        get_scenario,
        run_scenario,
        trace_to_spec,
    )

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--profile", default="short",
                    choices=("short", "full"),
                    help="short: seconds-scale tier-1-safe drills; "
                         "full: the real-length shapes (marked slow)")
    ap.add_argument("--scenario", action="append", default=[],
                    help="run only these (repeatable; default: whole atlas)")
    ap.add_argument("--replay", metavar="TRACE.json",
                    help="also replay a /v1/debug/capture trace file as "
                         "an extra scenario")
    ap.add_argument("--autopilot", default="off",
                    choices=("off", "on", "both"),
                    help="arm the closed-loop controllers: on = every "
                         "shape runs autopilot-armed; both = each shape "
                         "runs off AND on (same seed), the armed verdict "
                         "keyed '<name>@autopilot'")
    ap.add_argument("--out", help="artifact path (default: next SCEN_r<NN>)")
    ap.add_argument("--list", action="store_true",
                    help="print the atlas and exit")
    args = ap.parse_args(argv)

    if args.list:
        for name in SCENARIO_NAMES:
            spec = get_scenario(name)
            print(f"{name:20s} {spec.nodes}n "
                  f"{spec.duration_s():6.0f}s  {spec.description}")
        return 0

    names = args.scenario or list(SCENARIO_NAMES)
    verdicts = {}
    for name in names:
        if args.autopilot in ("off", "both"):
            print(f"scenario {name} [{args.profile}] ...", flush=True)
            v = run_scenario(get_scenario(name), profile=args.profile)
            verdicts[name] = v
            _print_verdict(v)
        if args.autopilot in ("on", "both"):
            key = name if args.autopilot == "on" else f"{name}@autopilot"
            print(f"scenario {key} [{args.profile}] autopilot ...",
                  flush=True)
            v = run_scenario(get_scenario(name), profile=args.profile,
                             autopilot=True)
            verdicts[key] = v
            _print_verdict(v)
    if args.replay:
        from gubernator_tpu.obs.capture import load_trace

        spec = trace_to_spec(load_trace(args.replay), name="replay")
        print(f"scenario replay [{args.replay}] ...", flush=True)
        v = run_scenario(spec, profile="short")
        verdicts["replay"] = v
        _print_verdict(v)

    doc = {
        "schema_version": 1,
        "profile": args.profile,
        "autopilot": args.autopilot,
        "scenarios": verdicts,
        "passed": all(v["passed"] for v in verdicts.values()),
    }
    out = args.out or _next_round_path()
    with open(out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    n_pass = sum(v["passed"] for v in verdicts.values())
    print(f"\n{n_pass}/{len(verdicts)} scenarios PASS -> {out}")
    return 0 if doc["passed"] else 1


def _print_verdict(v: dict) -> None:
    mark = "PASS" if v["passed"] else "FAIL"
    lat = v["stats"]["latency_ms"]
    print(f"  {mark}  goodput={v['goodput']:.4f} "
          f"over_limit={v['over_limit_share']:.3f} "
          f"err={v['error_share']:.4f} "
          f"p50={lat['p50']:.1f}ms p99={lat['p99']:.1f}ms")
    for c in v["checks"]:
        if not c["ok"]:
            print(f"        check {c['name']}: observed {c['observed']} "
                  f"vs threshold {c['threshold']}")
    if v["allowed_detectors_seen"]:
        print(f"        expected detectors seen: "
              f"{', '.join(v['allowed_detectors_seen'])}")


if __name__ == "__main__":
    sys.exit(main())
