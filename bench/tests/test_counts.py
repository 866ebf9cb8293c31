"""The benchmark's own operation and byte counts, from shapes."""
import pytest

from bench import counts, harness

PAPER = harness.resolve("femnist_paper_full").config
XDEV = harness.resolve("femnist_xdevice_paging").config
CNN = harness.load_module("models", "femnist_cnn")
#: one local step, one block, one gossip round: a round of a single pass
ONE_STEP = {"federation": dict(PAPER["federation"], tau=1, q=1, pi=1)}


def test_femnist_cnn_forward_flops_from_layer_shapes():
    # conv1 1,254,400 + conv2 20,070,400 + f1 12,845,056 + f2 253,952
    assert CNN.forward_flops(PAPER["model_sizes"]) == 34_423_808


def test_femnist_cnn_parameters_match_the_published_count():
    sizes = PAPER["model_sizes"]
    assert CNN.param_count(sizes) == sizes["params"] == 6_603_710


def test_model_flops_of_a_paper_round():
    # 3 x forward x batch 50 x 16 local steps x 64 rows = 5.29 TFLOP
    assert counts.model_flops(PAPER, CNN, 64) == 3 * 34_423_808 * 50 * 16 * 64


@pytest.mark.parametrize("cfg,passes", [(PAPER, 8), (ONE_STEP, 1)])
def test_mixing_passes_per_round(cfg, passes):
    assert counts.mix_passes(cfg["federation"]) == passes


@pytest.mark.parametrize("cfg", [PAPER, XDEV, ONE_STEP])
def test_mixing_passes_match_the_fused_lowering_plan(cfg):
    from repro.config import FLConfig
    from repro.core import program as prg
    fed = cfg["federation"]
    fl = FLConfig(algorithm="ce_fedavg", num_clusters=fed["num_clusters"],
                  devices_per_cluster=fed["devices_per_cluster"],
                  tau=fed["tau"], q=fed["q"], pi=fed["pi"])
    plans = prg.lowering_plan(prg.canonical_program(fl), fuse=True)
    assert sum(len(p.groups) for p in plans) == counts.mix_passes(fed)


def test_codec_bytes_of_the_paged_cohort():
    # 64 rows of T = 6,603,710, 8 leaves: int8 codes + f32 scales <-> f32
    encoded = 64 * 6_603_710 + 64 * 8 * 4
    assert counts.codec_bytes(64, 6_603_710, 8) == 2 * (
        encoded + 64 * 6_603_710 * 4) == 4_226_378_496


def test_mixing_bytes_and_flops_of_one_pass():
    assert counts.mix_bytes(64, 6_603_710) == 3_381_099_520 + 64 * 64 * 4
    assert counts.mix_flops(64, 6_603_710) == 2 * 64 * 64 * 6_603_710


def test_peaks_are_known_only_for_listed_chips():
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
