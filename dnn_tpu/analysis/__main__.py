"""CLI + CI gate: `python -m dnn_tpu.analysis`.

Runs the AST lint (trace/shard TPU rules + concurrency CON rules +
sharding SHD rules) over the package (plus any extra paths), the
protocol state-machine pass over the declared serving machines, the
device-free program pass over the real entrypoints, and the sharded-
program audit (shardcheck: memory bill, contract conformance,
allocation-sized collectives over the zero1/llama/pipeline/moe
programs), diffs everything against analysis/baseline.json, and exits
nonzero on any NEW finding.
Baselined findings are printed (enumerated, not hidden) with their
justification; baseline entries that no longer fire are reported stale.
`--diff REV` restricts the lint to package files changed since REV;
`--format sarif` emits SARIF 2.1.0 for CI annotation.

The pass is CPU-only by design: before jax loads we force the cpu
platform with 8 virtual host devices (the same harness tests/conftest.py
uses), so the program pass traces the mesh entrypoints on any host —
including CI runners, and without taking a chip another process holds.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def changed_files(rev: str, repo_root: str):
    """Repo-relative .py files changed between `rev` and the working
    tree (committed + staged + unstaged; deleted files excluded) — the
    `--diff` CI-annotation mode's file set."""
    out = subprocess.run(
        ["git", "-C", repo_root, "diff", "--name-only", rev, "--",
         "*.py"],
        capture_output=True, text=True, check=True).stdout
    files = []
    for rel in out.splitlines():
        rel = rel.strip()
        if rel and os.path.exists(os.path.join(repo_root, rel)):
            files.append(rel)
    return files


def sarif_report(new, suppressed, entries) -> dict:
    """SARIF 2.1.0 document for CI annotation (--format sarif): new
    findings as `error` results, baseline-suppressed ones carried as
    `note`s with their justification as an external suppression —
    enumerated, not hidden, same policy as the text report."""
    from dnn_tpu.analysis.findings import RULES

    just = {e["fingerprint"]: e.get("justification", "") for e in entries}
    used = sorted({f.rule for f in list(new) + list(suppressed)})
    rules = [{
        "id": rule,
        "shortDescription": {"text": RULES.get(rule, (rule, ""))[0]},
        "fullDescription": {"text": RULES.get(rule, ("", ""))[1]},
    } for rule in used]
    rule_index = {r: i for i, r in enumerate(used)}

    def result(f, *, suppressed_by=None):
        res = {
            "ruleId": f.rule,
            "ruleIndex": rule_index[f.rule],
            "level": "note" if suppressed_by is not None else "error",
            "message": {"text": f.message},
            "locations": [{"physicalLocation": {
                "artifactLocation": {"uri": f.path,
                                     "uriBaseId": "SRCROOT"},
                "region": {"startLine": max(f.line, 1)},
            }}],
            "partialFingerprints": {"dnnTpuAnalysis/v1": f.fingerprint},
        }
        if suppressed_by is not None:
            res["suppressions"] = [{"kind": "external",
                                    "justification": suppressed_by}]
        return res

    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "dnn_tpu.analysis",
                "informationUri": "dnn_tpu/analysis",
                "rules": rules,
            }},
            "results": [result(f) for f in new] + [
                result(f, suppressed_by=just.get(f.fingerprint, ""))
                for f in suppressed],
        }],
    }


def _force_cpu():
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if "jax" in sys.modules:
        # env alone is too late once jax is imported; backend init is
        # lazy though, so the config route still lands (conftest.py's
        # trick, reused here for in-process callers like the test suite)
        import jax

        jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    import dnn_tpu
    from dnn_tpu.analysis.findings import (
        RULES,
        assign_occurrences,
        diff_against_baseline,
        load_baseline,
        render_finding,
    )
    from dnn_tpu.analysis.lint import lint_paths

    pkg_dir = os.path.dirname(os.path.abspath(dnn_tpu.__file__))
    repo_root = os.path.dirname(pkg_dir)
    default_baseline = os.path.join(pkg_dir, "analysis", "baseline.json")

    ap = argparse.ArgumentParser(
        prog="python -m dnn_tpu.analysis",
        description="trace/shard-safety static analyzer (AST lint + "
                    "device-free jaxpr program checks)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the dnn_tpu "
                         "package)")
    ap.add_argument("--baseline", default=default_baseline,
                    help="suppression file (default: "
                         "dnn_tpu/analysis/baseline.json)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report everything as new)")
    ap.add_argument("--no-program", action="store_true",
                    help="skip the jaxpr program pass (pure AST lint — "
                         "no jax import)")
    ap.add_argument("--no-protocol", action="store_true",
                    help="skip the protocol state-machine pass "
                         "(analysis/protocol.py)")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="changed-files-only mode: lint only the "
                         "PACKAGE .py files that differ from REV (git "
                         "diff REV, filtered to dnn_tpu/ — the same "
                         "scope as the default gate; tests/benchmarks "
                         "plant hazard fixtures on purpose); implies "
                         "--no-program and skips stale-baseline "
                         "reporting (most entries legitimately don't "
                         "fire on a partial file set)")
    ap.add_argument("--format", choices=("text", "sarif"),
                    default="text",
                    help="report format; sarif emits a SARIF 2.1.0 "
                         "document on stdout for CI annotation")
    ap.add_argument("--max-len", type=int, default=128,
                    help="cache allocation the decode census sweeps to "
                         "(default 128)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings "
                         "(justifications of kept entries are preserved; "
                         "new entries get a fill-me-in marker)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule, (title, desc) in RULES.items():
            print(f"{rule}  {title}\n    {desc}")
        return 0

    if args.diff is not None:
        # changed-files-only (CI annotation on a PR diff): the AST +
        # concurrency lints are per-file-sound, so a partial file set
        # is exact for them; the whole-program jaxpr pass is not and
        # is skipped (run the full gate for it)
        pkg_rel = os.path.basename(pkg_dir)
        try:
            lint_targets = [
                os.path.join(repo_root, rel)
                for rel in changed_files(args.diff, repo_root)
                if rel == pkg_rel or rel.startswith(pkg_rel + "/")]
        except subprocess.CalledProcessError as e:
            print(f"--diff {args.diff}: git diff failed: "
                  f"{e.stderr or e}", file=sys.stderr)
            return 2
        args.no_program = True
    else:
        lint_targets = args.paths or [pkg_dir]
    findings = list(lint_paths(lint_targets, repo_root=repo_root))

    protocol_report = None
    if not args.no_protocol:
        # protocol pass: pure-AST over the declared machines' modules —
        # whole-repo-sound and cheap, so it runs even in --diff mode
        from dnn_tpu.analysis.protocol import run_protocol_audit

        protocol_report, proto_findings = run_protocol_audit(repo_root)
        findings = assign_occurrences(findings + list(proto_findings))

    program_report = None
    shard_report = None
    if not args.no_program:
        _force_cpu()
        from dnn_tpu.analysis.program import run_program_audit
        from dnn_tpu.analysis.shardcheck import run_shard_audit

        program_report, prog_findings = run_program_audit(
            max_len=args.max_len)
        shard_report, shard_findings = run_shard_audit()
        findings = assign_occurrences(
            findings + list(prog_findings) + list(shard_findings))

    entries = []
    if not args.no_baseline and os.path.exists(args.baseline):
        entries = load_baseline(args.baseline)
    new, suppressed, stale = diff_against_baseline(findings, entries)

    if args.write_baseline:
        kept = {e["fingerprint"]: e for e in entries}
        out = {"suppressions": [
            kept.get(f.fingerprint, {
                "fingerprint": f.fingerprint,
                "rule": f.rule, "path": f.path, "snippet": f.snippet,
                "justification": "(unjustified — explain why this "
                                 "finding stays, or fix it)",
            }) for f in findings]}
        with open(args.baseline, "w") as fh:
            json.dump(out, fh, indent=2)
            fh.write("\n")
        print(f"wrote {len(findings)} entries to {args.baseline}")
        return 0

    if args.diff is not None:
        stale = []  # partial file set: silence is expected, not stale

    if args.format == "sarif":
        print(json.dumps(sarif_report(new, suppressed, entries),
                         indent=2))
        return 1 if new else 0

    if args.as_json:
        print(json.dumps({
            "new": [vars(f) | {"fingerprint": f.fingerprint} for f in new],
            "suppressed": [vars(f) | {"fingerprint": f.fingerprint}
                           for f in suppressed],
            "stale_baseline": stale,
            "program_report": program_report,
            "shard_report": shard_report,
            "protocol_report": protocol_report,
        }, indent=2, default=str))
        return 1 if new else 0

    if protocol_report is not None:
        mk = protocol_report["machines"]
        print("protocol pass: "
              + ", ".join(f"{m['name']}({m['states']}s/{m['edges']}e"
                          f"{'' if m['clean'] else ' DRIFT'})"
                          for m in mk))
    if program_report is not None:
        dec = program_report.get("decode", {})
        print("program pass:")
        print(f"  decode donation: "
              f"{dec.get('donation', {}).get('aliased')}/"
              f"{dec.get('donation', {}).get('expected')} cache buffers "
              "aliased")
        sd = program_report.get("serving_decode", {}).get("variants", {})
        if sd:
            parts = [f"{k}={v['aliased']}/{v['expected']}"
                     for k, v in sd.items()]
            print("  serving decode donation (aliased/donated, zero "
                  "cache-sized copies asserted): " + ", ".join(parts))
        bc = dec.get("bucketed_census", {})
        nc = dec.get("naive_census", {})
        print(f"  bucketed decode census: {bc.get('programs')} programs "
              f"for {bc.get('calls')} steps (ladder bound "
              f"{bc.get('bound')}; naive exact-length dispatch: "
              f"{nc.get('programs')})")
        pipe = program_report.get("pipeline", {})
        print(f"  pipeline stage collective signature: "
              f"{pipe.get('collective_signature')}")
        tp = program_report.get("transport", {})
        print(f"  transport hop program ({tp.get('stages')} stages) "
              f"collective signature: {tp.get('collective_signature')}")
        eng = program_report.get("engine", {})
        print(f"  engine[{eng.get('runtime')}] batch census: "
              f"{eng.get('batch_census', {}).get('programs')} programs "
              f"/ {eng.get('batch_census', {}).get('calls')} batch "
              "shapes")
    if shard_report is not None:
        print("shard pass:")
        for name in ("zero1", "llama_dp_tp"):
            sec = shard_report.get(name, {})
            bill = sec.get("bill", {}).get("params", {})
            col = sec.get("collectives", {})
            line = (f"  {name}{sec.get('mesh')}: params bill "
                    f"{bill.get('actual_per_device_bytes')}/"
                    f"{bill.get('expected_per_device_bytes')} B/device "
                    f"({len(bill.get('mismatches', []))} mismatches), "
                    f"largest collective "
                    f"{col.get('largest_frac', 0):.2f}x of "
                    "tree-frac threshold "
                    f"{col.get('threshold_frac')}")
            print(line)
        z = shard_report.get("zero1", {})
        don = z.get("donation", {})
        print(f"  zero1 donation under NamedSharding: "
              f"{don.get('aliased')}/{don.get('expected')} sharded "
              "buffers aliased; sharding census "
              f"{z.get('sharding_census', {}).get('programs')} programs"
              f"/{z.get('sharding_census', {}).get('calls')} calls "
              f"(bound {z.get('sharding_census', {}).get('bound')})")
        pl = shard_report.get("pipeline_stacked", {})
        moe = shard_report.get("moe_ep", {})
        print(f"  stacked pipeline placement bill: "
              f"{pl.get('bill', {}).get('stacked', {}).get('mismatches')}"
              " mismatches; moe EP axis signature: "
              f"{moe.get('collective_signature')}")
    if suppressed:
        just = {e["fingerprint"]: e.get("justification", "")
                for e in entries}
        print(f"\n{len(suppressed)} baseline-suppressed finding(s) "
              "(known, justified, NOT hidden):")
        for f in suppressed:
            print(f"  {f.path}:{f.line} {f.rule} — "
                  f"{just.get(f.fingerprint, '')}")
    if stale:
        print(f"\n{len(stale)} stale baseline entr(y/ies) — the finding "
              "no longer fires; delete from baseline.json:")
        for e in stale:
            print(f"  {e['fingerprint']} ({e.get('path', '?')})")
    if new:
        print(f"\n{len(new)} NEW finding(s):")
        for f in new:
            print(render_finding(f))
        print("\nFAIL: new findings above are not in the baseline. Fix "
              "them, or (with a written justification) add them to "
              f"{args.baseline}.")
        return 1
    print(f"\nOK: no new findings ({len(findings)} total, "
          f"{len(suppressed)} baselined).")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # `... | head` closed stdout mid-report
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
