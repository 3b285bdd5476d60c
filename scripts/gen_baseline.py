"""Regenerate BASELINE.md's measured section from the newest BENCH json.

The r3 verdict (item 10) caught BASELINE.md disagreeing with
BENCH_r03.json — hand-transcribed numbers drift. This script makes the
bench JSON the single source of truth: everything between the
``<!-- BENCH:BEGIN -->`` / ``<!-- BENCH:END -->`` markers is replaced by
tables rendered from the latest ``BENCH_r*.json`` (or an explicit path).

    python scripts/gen_baseline.py [path/to/BENCH.json]
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BEGIN, END = "<!-- BENCH:BEGIN -->", "<!-- BENCH:END -->"


def latest_bench() -> Path:
    # deterministic pick (git checkouts flatten mtimes): highest round
    # number wins; within a round the driver's BENCH_rNN.json beats the
    # builder's mid-round BENCH_rNN_builder.json; mtime only tiebreaks
    def key(p: Path):
        m = re.search(r"r(\d+)", p.name)
        return (int(m.group(1)) if m else -1,
                0 if "_builder" in p.name else 1,
                p.stat().st_mtime)

    candidates = sorted(REPO.glob("BENCH_r*.json"), key=key)
    if not candidates:
        raise SystemExit("no BENCH_r*.json found")
    return candidates[-1]


def render(path: Path) -> str:
    rec = json.loads(path.read_text())
    b = rec.get("parsed", rec)
    if "value" not in b:
        raise SystemExit(
            f"{path.name} is not a bench record (no 'value' key)")

    def row(label, key, unit=""):
        v = b.get(key)
        return None if v is None else f"| {label} | {v}{unit} |"

    lines = [
        BEGIN,
        f"*(generated by `scripts/gen_baseline.py` from `{path.name}`; "
        "do not edit between the markers)*",
        "",
    ]
    if b.get("nnz") is not None:
        sweeps = b.get("sweeps") or 0
        bf16 = b.get("bf16_sweeps") or 0
        lines += [
            f"Workload: synthetic ML-20M shape (nnz={b['nnz']:,}, "
            f"rank={b.get('rank')}, {sweeps} sweeps, {bf16} bf16 + "
            f"{sweeps - bf16} f32-polish).",
            "",
        ]
    if b.get("degraded"):
        if b.get("degraded_nnz"):
            provenance = ("train quality below is the all-f32 CPU "
                          f"schedule on a {b['degraded_nnz']:,}-event "
                          "subsample (host-stage walls are full-shape)")
        else:
            provenance = ("the degraded fallback produced no train-"
                          "quality numbers either; host-stage walls only")
        lines += [
            "**DEGRADED record** — the accelerator never became "
            f"available; {provenance}.",
            "",
        ]
    sections = [
        ("Training", [
            row("warm fused train wall", "value", " s"),
            row("vs measured CPU baseline", "vs_baseline", "×"),
            row("MFU (f32-peak denominator)", "mfu"),
            row("MFU (bf16-peak denominator)", "mfu_bf16_peak"),
            row("train RMSE (fit)", "train_rmse"),
            row("heldout RMSE (fresh draws)", "heldout_rmse"),
            row("planted noise floor", "noise_floor"),
            row("precision@10 vs true ranking", "precision_at_10_vs_truth"),
            row("compile first call", "compile_s_first", " s"),
            row("compile warm persistent cache", "compile_s_warm_cache",
                " s"),
        ]),
        ("pio train pipeline", [
            row("seed (native bulk import)", "seed_wall_s", " s"),
            row("ingest scan (projection-cached)", "ingest_wall_s", " s"),
            row("prep (bucketed padded rows)", "prep_wall_s", " s"),
            row("e2e train wall (scan+prep+train)", "e2e_train_wall_s",
                " s"),
        ]),
        ("Serving / ingest (real HTTP servers)", [
            row("sequential p50", "serve_p50_ms", " ms"),
            row("sequential p99", "serve_p99_ms", " ms"),
            row("sequential QPS", "serve_qps"),
            row("concurrent QPS (128 async clients)",
                "serve_qps_concurrent"),
            row("max micro-batch served", "serve_max_batch"),
            row("EventServer REST ingest", "ingest_http_eps", " ev/s"),
            row("REST ingest, --batch-cap 500",
                "ingest_http_eps_cap500", " ev/s"),
        ]),
        ("Real-data bound (reference movielens sample)", [
            row("heldout RMSE", "movielens_rmse"),
            row("pinned bound", "movielens_rmse_bound"),
        ]),
        ("Long-context attention", [
            row("flash kernel active", "flash_kernel_active"),
            row("flash 4k fwd", "attn_flash_ms_4k", " ms"),
            row("XLA blockwise 4k fwd", "attn_xla_ms_4k", " ms"),
            row("flash 8k fwd", "attn_flash_ms_8k", " ms"),
            row("XLA blockwise 8k fwd", "attn_xla_ms_8k", " ms"),
            row("flash 32k fwd", "attn_flash_ms_32k", " ms"),
            row("XLA blockwise 32k fwd", "attn_xla_ms_32k", " ms"),
            row("SASRec 1-epoch wall (512×128)", "sasrec_epoch_s", " s"),
        ]),
    ]
    missing = []
    for title, rows in sections:
        present = [r for r in rows if r is not None]
        if not present:
            missing.append(title)
            continue
        lines += [f"| {title} | value |", "|---|---|", *present, ""]
    if missing:
        lines.append(
            f"*(sections absent from {path.name}: {', '.join(missing)})*")
    while lines and lines[-1] == "":
        lines.pop()
    lines.append(END)
    return "\n".join(lines)


def main() -> None:
    src = Path(sys.argv[1]) if len(sys.argv) > 1 else latest_bench()
    baseline = REPO / "BASELINE.md"
    text = baseline.read_text()
    block = render(src)
    if BEGIN in text:
        text = re.sub(
            re.escape(BEGIN) + ".*?" + re.escape(END), lambda _m: block,
            text, flags=re.S)
    else:
        text = text.rstrip() + "\n\n## Measured (latest bench)\n\n" + block + "\n"
    baseline.write_text(text)
    print(f"BASELINE.md regenerated from {src.name}")


if __name__ == "__main__":
    main()
