"""The sequence cell at a tiny size on the CPU: the configuration's file
against its source and its own block description, the work counted from
its shapes, the control one precision down against the file's limits,
and the rehearsal of the whole run with a fault planted under the
forward."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "mellum2-12b-l4-seq2048"
CELL = NAME + ".serve-steady-wide"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config() -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as f:
        return json.load(f)


def tiny() -> dict:
    """The configuration cut to a size the CPU runs: the published
    pattern (three sliding layers and one full, two rotary kinds, routed
    experts) at hidden 64."""
    rope = {"full_attention": {
        "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
    yarn = rope["full_attention"]
    sliding = {"window": 8, "rotary": {"theta": 10000}}
    block = {
        "dModel": 64, "nHeads": 4, "nKvHeads": 2, "headDim": 16,
        "period": [sliding, sliding, sliding, {"rotary": {
            "theta": 10000, "factor": yarn["factor"],
            "originalMaxPosition": 16, "betaFast": 32, "betaSlow": 1,
            "attentionFactor": yarn["attention_factor"]}}],
        "nPeriods": 1, "ffn": "routed-swiglu", "ffnWidth": 32,
        "nExperts": 8, "expertsPerToken": 2, "learnedPositions": False,
        "tiedHead": False, "dtype": "float32", "normEps": 1e-6,
        "maxLen": 24}
    return {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_intermediate_size": 32,
        "sliding_window": 8, "vocab_size": 400, "rope_parameters": rope,
        "window_events": 24, "n_users": 300, "n_items": 400, "rank": 64,
        # float32 here: at hidden 64 with 8 experts one routed expert that
        # flips on bfloat16 rounding moves a logit by a fifth of the
        # spread, which says nothing of the published widths
        "dtype": "float32",
        "algorithm": {"name": "sasrec", "params": {
            "appName": "BenchApp", "block": block}},
    }


def test_the_file_holds_the_source_and_cuts_only_the_depth():
    cfg = config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == NAME)
    assert entry["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == entry["reduced"] == ["num_hidden_layers"]
    # one whole period of the published pattern runs
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == [
        "sliding_attention"] * 3 + ["full_attention"]


def test_the_block_the_engine_reads_is_the_one_the_file_states():
    from incubator_predictionio_tpu.ops import transformer

    cfg = config()
    spec = transformer.block_spec_from_json(
        cfg["algorithm"]["params"]["block"])
    full = cfg["rope_parameters"]["full_attention"]
    assert (spec.d_model, spec.n_heads, spec.n_kv_heads, spec.head_dim) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    assert (spec.n_experts, spec.experts_per_token, spec.ffn_width) == (
        cfg["num_experts"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"])
    assert spec.n_layers == cfg["num_hidden_layers"]
    assert [ls.window for ls in spec.period] == [cfg["sliding_window"]] * 3 \
        + [None]
    assert spec.period[0].rotary == transformer.Rotary(theta=500000.0)
    assert spec.period[3].rotary == transformer.Rotary(
        theta=full["rope_theta"], factor=full["factor"],
        original_max_position=full["original_max_position_embeddings"],
        beta_fast=full["beta_fast"], beta_slow=full["beta_slow"],
        attention_factor=full["attention_factor"])
    assert (spec.max_len, spec.norm_eps, spec.dtype, spec.tied_head) == (
        cfg["window_events"], cfg["rms_norm_eps"], cfg["dtype"],
        cfg["tie_word_embeddings"])
    assert (cfg["n_items"], cfg["rank"]) == (cfg["vocab_size"],
                                             cfg["hidden_size"])


def test_work_is_the_issues_arithmetic():
    from benchmark import seqwork

    cfg = config()
    # 141.9 MFLOP a token a layer in matmuls, 99.1 of them in the experts
    assert seqwork.moe_query_flops(cfg) / (4 * 2048) == pytest.approx(
        99.1e6, rel=1e-3)
    # attention: 25.8 GFLOP a sliding layer, 34.4 a full one; the head
    attention = 3 * 25.78e9 + 34.38e9
    assert seqwork.query_flops(cfg) == pytest.approx(
        4 * 2048 * 141.86e6 + attention + 2 * 2304 * 98304, rel=1e-3)
    assert seqwork.query_flops(cfg) == pytest.approx(1.27e12, rel=0.01)
    v5e = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 3.8 GB of weights read once a dispatch; one query sits by the ridge
    assert seqwork.dispatch_bytes(cfg, 1, 0, 16) == pytest.approx(
        3.80e9, rel=0.01)
    least, bound = seqwork.least_seconds(cfg, v5e, 1, 1, 16)
    assert bound == "compute" and least == pytest.approx(6.45e-3, rel=0.01)
    assert seqwork.least_seconds(cfg, v5e, 1, 8, 16)[1] == "compute"
    # the grouped products alone: 3.2 GB of tables against 0.81 TFLOP a
    # query, so one query is by the ridge and a fused batch computes
    assert seqwork.moe_least_seconds(cfg, v5e, 1, 1)[1] == "memory"
    assert seqwork.moe_least_seconds(cfg, v5e, 1, 2)[1] == "compute"


def test_seeded_tensors_are_the_same_alone_and_in_the_table():
    from benchmark import seqmodel

    whole = np.asarray(seqmodel.make_all_windows(2**31 + 5, 40, 24, 400))
    some = np.asarray(seqmodel.make_windows(2**31 + 5, [3, 17, 39], 24, 400))
    assert np.array_equal(whole[[3, 17, 39]], some)
    assert whole.min() >= 1 and whole.max() < 400
    other = np.asarray(seqmodel.make_windows(2**31 + 6, [3, 17, 39], 24, 400))
    assert not np.array_equal(some, other)
    a = seqmodel.layer_tensors(9, 0, 64, 64, 32, 8, 32)
    b = seqmodel.layer_tensors(9, 1, 64, 64, 32, 8, 32)
    assert a["w_gate"].shape == (8, 64, 32) and a["wq"].dtype.name == \
        "bfloat16"
    assert not np.array_equal(np.asarray(a["wq"], np.float32),
                              np.asarray(b["wq"], np.float32))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_control_one_precision_down_is_not_correct(seed):
    from benchmark.reference import mellum2_topk

    cfg = {**config(), **tiny()}
    rows = np.random.default_rng(seed).choice(cfg["n_users"], 12,
                                              replace=False)
    sound = mellum2_topk.control(cfg, seed, rows, 10, "float32")
    assert sound["score_err"] == 0.0 and sound["rank_gap"] == 0.0
    assert mellum2_topk.judge(sound, cfg["limits"]), sound
    low = mellum2_topk.control(cfg, seed, rows, 10)
    assert not mellum2_topk.judge(low, cfg["limits"]), low


def test_a_fault_in_a_minority_of_the_users_is_not_correct():
    """The medians pass while one user is given another's answer (a wrong
    row of a fused batch) or five of twelve are a tenth of the spread off:
    the widest user and the share of users off fail the run."""
    from benchmark.reference import mellum2_topk

    cfg = {**config(), **tiny()}
    rows = np.arange(1, 13)
    top_s, top_i = mellum2_topk.top_k(cfg, 7, rows, 10)
    sound = [(i.tolist(), s.tolist()) for s, i in zip(top_s, top_i)]
    assert mellum2_topk.judge(
        mellum2_topk.compare(cfg, 7, rows, sound, 10), cfg["limits"])
    model = mellum2_topk.Model(cfg, 7)
    seen = set(model.windows([1])[0].tolist())
    other = next(a for a in sound[1:] if not seen & set(a[0]))
    swapped = mellum2_topk.compare(cfg, 7, rows, [other] + sound[1:], 10)
    assert swapped["score_err"] <= cfg["limits"]["score_err"]
    assert swapped["score_err_max"] > cfg["limits"]["score_err_max"]
    assert not mellum2_topk.judge(swapped, cfg["limits"]), swapped
    spread = [float(np.std(l[np.isfinite(l)])) for l in
              (model.last_logits(w) for w in model.windows(rows[:5]))]
    shifted = [(a[0], [x + 0.1 * sd for x in a[1]])
               for a, sd in zip(sound, spread)] + sound[5:]
    off = mellum2_topk.compare(cfg, 7, rows, shifted, 10)
    assert off["score_err"] <= cfg["limits"]["score_err"]
    assert off["users_off"] == pytest.approx(5 / 12)
    assert off["score_err_max"] < cfg["limits"]["score_err_max"]
    assert mellum2_topk.judge(off, cfg["limits"])       # 5 of 12: 0.4167
    shifted = shifted[:5] + [(sound[5][0], [x + 1.0 for x in sound[5][1]])] \
        + sound[6:]
    off = mellum2_topk.compare(cfg, 7, rows, shifted, 10)
    assert off["users_off"] == pytest.approx(6 / 12)
    assert not mellum2_topk.judge(
        off, {**cfg["limits"], "score_err_max": 99}), off


def test_malformed_answers_are_counted():
    from benchmark.reference import mellum2_topk

    cfg = {**config(), **tiny()}
    rows = np.array([1, 2, 3, 3])
    top_s, top_i = mellum2_topk.top_k(cfg, 5, rows, 10)
    answers = [(i.tolist(), s.tolist()) for s, i in zip(top_s, top_i)]
    window = mellum2_topk.Model(cfg, 5).windows([1])[0]
    answers[0] = ([int(window[0])] + answers[0][0][1:], answers[0][1])
    answers[1] = None
    numbers = mellum2_topk.compare(cfg, 5, rows, answers, 10)
    assert numbers["malformed"] == 2 and numbers["compared"] == 2
    assert numbers["users"] == 1
    assert not mellum2_topk.judge(numbers, {"score_err": 1, "rank_gap": 1})
    body = json.dumps({"itemScores": [{"item": "i7", "score": 1.5}]})
    assert mellum2_topk.parse_answer(body.encode()) == ([7], [1.5])
    assert mellum2_topk.parse_answer(b"{}") is None


REHEARSAL = r"""
import json, os, sys
sys.path.insert(0, os.getcwd())
sys.path.insert(0, os.path.join(os.getcwd(), "benchmark", "tests"))
import test_mellum2
from benchmark import run

fault = sys.argv[1]
if fault != "none":
    import jax.numpy as jnp
    from incubator_predictionio_tpu.ops import transformer
    inner = transformer.block_apply

    def broken(spec, w, tokens, attn_fn=None):
        hidden, routed = inner(spec, w, tokens, attn_fn)
        if fault == "scale_hidden":       # every logit 5% off
            return hidden * jnp.asarray(1.05, hidden.dtype), routed
        if fault == "drop_layer":         # the last layer's residual lost
            return inner(spec.__class__(**{**spec.__dict__, "period":
                         spec.period[:3] + (spec.period[0],)}), w, tokens,
                         attn_fn)
        raise SystemExit(fault)

    transformer.block_apply = broken
result, rc = run.run_cell(
    test_mellum2.CELL, 2**31 + 21, 3.0, bool(int(sys.argv[2])),
    rehearsal={"config": test_mellum2.tiny(), "rate": 40.0,
               "env": {"PIO_SERVE_MAX_BATCH": "4"}})
print(json.dumps(result), flush=True)
if rc == run.LEFTOVER:
    sys.stderr.flush()
    os._exit(0)
sys.exit(rc)
"""


def rehearse(fault: str, trace: int = 0):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", REHEARSAL, fault, str(trace)],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-3000:] + p.stdout[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


def test_rehearsal_reports_the_end_to_end_metrics_and_is_correct():
    result, out = rehearse("none")
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 120
    assert set(result["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"   # a rehearsal says so
    c = result["compared"]
    assert c["compared"]["value"] >= 1 and c["malformed"]["value"] == 0
    assert c["score_err"]["value"] <= c["score_err"]["limit"]
    # the scheduler fused: the engine served through batch_serve_json
    window = json.loads(next(ln for ln in out.splitlines()
                             if ln.startswith("window: "))[8:])
    assert window["compiles_in_window"] == 0
    assert window["batch_size_mean"] >= 1.0


def test_traced_rehearsal_reads_the_counters_the_cpu_can():
    result, _out = rehearse("none", trace=1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [CELL])}
    assert set(result["metrics"]) <= mine
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert {"batch_size_mean", "dispatch_host_ms", "dispatch_fetch_ms",
            "dispatch_render_ms", "dispatcher_wait_pct",
            "compiles_in_window"} <= set(result["metrics"])
    # no TPU plane here: the device readers return nothing, not 0; the
    # ALS readers are not this cell's
    assert not {"seq_forward_roofline", "moe_experts_roofline",
                "score_roofline", "serve_mfu_pct"} & set(result["metrics"])


@pytest.mark.parametrize("fault", ["scale_hidden", "drop_layer"])
def test_a_fault_planted_under_the_forward_is_not_correct(fault):
    result, _out = rehearse(fault)
    assert result["correct"] is False
    assert result["failed"] == 0          # well-formed, in time — and wrong
    c = result["compared"]
    assert (c["score_err"]["value"] > c["score_err"]["limit"]
            or c["rank_gap"]["value"] > c["rank_gap"]["limit"])


# -- the four readers against a hand-made trace and two scrapes -------------

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader_context(cfg):
    """Two executions of the forward in a 1 s traced window that answered
    12 queries: 0.2 s each on the device, 0.05 s of it in the experts'
    kernel (four layers); a consumer whose operands name the kernel is
    not it."""
    ops, mods = [], []
    for n in range(2):
        t0 = 1e9 * (0.1 + 0.4 * n)
        mods.append(["jit_block_top_k_rows(77)", t0, 0.2e9])
        for j in range(4):
            ops.append([f"%pio_moe_experts.{j} = bf16[131072,2304]{{1,0}} "
                        f"custom-call(%fusion.{j})", t0 + j * 1e7,
                        0.05e9 / 4])
        ops.append(["%fusion.9 = bf16[131072,2304]{1,0} "
                    "fusion(%pio_moe_experts.3)", t0 + 1e8, 0.1e9])
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops}]}]}
    done = np.r_[np.linspace(2.1, 2.9, 12), [0.5, 3.5]]
    before = {("pio_seq_moe_expert_tokens_total",
               frozenset({("expert", str(e))})): 100.0 for e in range(4)}
    after = {k: v + (300.0 if dict(k[1])["expert"] == "2" else 100.0)
             for k, v in before.items()}
    return {"config": cfg, "peaks": V5E, "chips": 1, "trace": trace,
            "trace_window": (2.0, 3.0), "window": (2.0, 53.0),
            "mix": {"query": {"num": 10}},
            "log": {"good": np.ones(14, bool), "done": done},
            "scrape0": before, "scrape1": after}


def test_the_four_readers_by_hand():
    from benchmark import run, seqwork

    cfg = config()
    ctx = reader_context(cfg)
    flops = seqwork.query_flops(cfg)
    assert run.read_metric("seq_serve_mfu_pct", ctx) == pytest.approx(
        100 * 12 * flops / 197e12)
    # 12 queries in 2 executions: compute-bound, 12 × 6.45 ms over 0.4 s
    assert run.read_metric("seq_forward_roofline", ctx) == pytest.approx(
        100 * 12 * flops / 197e12 / 0.4)
    assert run.read_metric("moe_experts_roofline", ctx) == pytest.approx(
        100 * 12 * seqwork.moe_query_flops(cfg) / 197e12 / 0.1)
    # expert 2 took 300 of the window's 600 tokens: twice the mean
    assert run.read_metric("moe_load_max_over_mean", ctx) == pytest.approx(
        2.0)


@pytest.mark.parametrize("name", ["seq_serve_mfu_pct", "seq_forward_roofline",
                                  "moe_experts_roofline",
                                  "moe_load_max_over_mean"])
def test_a_program_without_the_block_reads_as_nothing(name):
    """On the parent's program (or in a cell of another engine) the
    module, the ops and the counters are not there: every reader returns
    nothing and none raises."""
    from benchmark import run

    ctx = reader_context(config())
    for line in ctx["trace"]["planes"][0]["lines"]:
        line["events"] = [e for e in line["events"]
                          if "gmm" not in e[0] and "block_top_k" not in e[0]]
    ctx["scrape0"] = ctx["scrape1"] = {}
    if name == "seq_serve_mfu_pct":
        ctx["log"]["good"][:] = False     # nothing answered in the trace
    assert run.read_metric(name, ctx) is None
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "als-msd-d2048.json")) as f:
        als = {**reader_context(json.load(f)), "scrape0": {}, "scrape1": {}}
    assert run.read_metric(name, als) is None
