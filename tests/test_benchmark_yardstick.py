"""What the benchmark measures by lives partly in the program: the
reduction from trace to ``score_roofline`` (the factor engines) and to
``seq_forward_roofline`` (the sequence engine) finds the scoring program
by its XLA module name (``benchmark/configs/*.json`` ``scoring_module``).
A rename of the jitted function would leave the tests green and blind
the metric on the chip; this fails here instead."""

import glob
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "benchmark", "configs",
                                        "*.json")))


def test_the_benchmark_has_configurations():
    assert CONFIGS


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_scoring_module_names_the_jitted_scoring_program(path):
    from incubator_predictionio_tpu.ops import topk, transformer

    with open(path) as f:
        config = json.load(f)
    # XLA names a jitted function's module "jit_" + its __name__; a
    # configuration names the fused dispatch of its own engine
    program = {
        "RecommendationEngine": topk._batch_score_top_k_xla,
        "SequenceEngine": transformer.block_top_k_rows,
    }[config["engine_factory"].rsplit(":", 1)[1]]
    assert config["scoring_module"] in "jit_" + program.__name__
    assert transformer.block_top_k_rows in topk._COUNTED_PROGRAMS
