"""Moonlight-16B-A3B at its published config.json, and the traffic derived
from it (``repro.workloads``).

* The registry config holds the published values: 27 layers of MLA, the
  first dense, 64 routed experts (top-6) and 2 shared experts on the other
  26, untied vocabulary of 163840. Its parameter counts agree with the
  ones reckoned from the config.json widths: 15.96 B in all, 2.91 B active.
* The program's ``train.fwd`` matrix equals the plain reference
  ``chip_bench/model_traffic_ref.py`` (NumPy, nothing of the program) at
  16, 64 and 256 tiles.
* The fields Moonlight added leave every other registry model's traffic
  bit-identical: the fingerprints below are the matrices of commit
  3f6524dd0748a477f82d7db360399035b632ddc3, the last before them.
"""

import dataclasses
import hashlib
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config
from repro.configs.registry import ALIASES
from repro.core import spec_16, spec_64
from repro.core.problem import spec_large
from repro.workloads import PHASES, scenario_matrix
from repro.workloads.traffic_model import (_kv_bytes_per_token,
                                           _tp_allreduces)

_REF = Path(__file__).resolve().parents[1] / "chip_bench" / \
    "model_traffic_ref.py"
_spec = importlib.util.spec_from_file_location("model_traffic_ref", _REF)
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

NAME = "moonlight-16b-a3b"


def test_config_holds_the_published_values():
    c, p = get_config(NAME), REF.PUBLISHED
    assert c.family == "moe"
    assert (c.n_layers, c.n_dense_layers) == (p["num_hidden_layers"],
                                              p["first_k_dense_replace"])
    assert (c.d_model, c.d_ff, c.moe_d_ff) == (
        p["hidden_size"], p["intermediate_size"],
        p["moe_intermediate_size"])
    assert (c.n_experts, c.top_k, c.n_shared_experts) == (
        p["n_routed_experts"], p["num_experts_per_tok"],
        p["n_shared_experts"])
    assert (c.n_heads, c.n_kv_heads) == (p["num_attention_heads"],
                                         p["num_key_value_heads"])
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (p["kv_lora_rank"], p["qk_nope_head_dim"],
                              p["qk_rope_head_dim"], p["v_head_dim"])
    assert p["q_lora_rank"] is None          # full-rank queries
    assert (c.vocab, c.tie_embeddings) == (p["vocab_size"],
                                           p["tie_word_embeddings"])
    assert (c.rope_theta, c.norm_eps) == (p["rope_theta"], p["rms_norm_eps"])


def test_parameter_counts_match_the_published_widths():
    c = get_config(NAME)
    assert c.param_count() == pytest.approx(15.96e9, rel=0.01)
    assert c.active_param_count() == pytest.approx(2.91e9, rel=0.02)
    assert c.param_count() == REF.param_count()
    assert c.active_param_count() == REF.active_param_count()


def test_old_name_is_an_alias_outside_the_registry():
    assert NAME in ARCH_NAMES
    assert "moonshot-v1-16b-a3b" not in ARCH_NAMES
    assert ALIASES["moonshot-v1-16b-a3b"] == NAME
    assert get_config("moonshot-v1-16b-a3b") is get_config(NAME)
    spec = spec_16()
    assert np.array_equal(
        scenario_matrix(spec, "moonshot-v1-16b-a3b", "train.fwd"),
        scenario_matrix(spec, NAME, "train.fwd"))


def test_published_structure_sets_the_volumes():
    c = get_config(NAME)
    assert _tp_allreduces(c) == 26 + 2      # MoE attention; dense attn + MLP
    assert _kv_bytes_per_token(c) == 27 * (512 + 64) * 2.0   # MLA latent


@pytest.mark.parametrize("spec_fn", [spec_16, spec_64, spec_large])
def test_train_fwd_matches_the_plain_reference(spec_fn):
    spec = spec_fn()
    got = scenario_matrix(spec, NAME, "train.fwd")
    want = REF.train_fwd(dataclasses.asdict(spec))
    assert REF.rel_gap(got, want) <= 1e-12


#: sha256 (first 16 hex digits) of scenario_matrix(spec_64(), arch, phase)
#: .tobytes() at commit 3f6524dd0748a477f82d7db360399035b632ddc3
PARENT_FINGERPRINTS = {
    "mistral-large-123b": ("47e685b8a6fcb64b", "a625202bfff0782d",
                           "05280f2a435cff37", "a078a93e4379d4a0",
                           "10ea9cf55dccbcaf"),
    "gemma3-1b": ("00f1315bad13a2cd", "b8851a7fcd4f29c3", "6b9419ec83e0027f",
                  "e1aa552d20758847", "0ec6096bf0ab4937"),
    "deepseek-coder-33b": ("43ed6a7b420bd72d", "5f7c15106941782d",
                           "2784ce433910fa00", "ff242f809f4a1fc2",
                           "ae9ec43fa67cb84d"),
    "yi-6b": ("22ef43e3fcf3bac5", "9831159ea72c6624", "6be35899c6062f5e",
              "39bfad0b864cc49a", "14b7bea1d43249d6"),
    "qwen3-moe-30b-a3b": ("35468c62aa3f2d6c", "7d8f1df7eab38e90",
                          "00908593d98568b9", "b672fe4d8eb8348c",
                          "263f6b4d6f769805"),
    "zamba2-2.7b": ("efb3c0373442561a", "b37ab8c0313eda6f",
                    "341f4b3c1f03914f", "a782df977cd0eb05",
                    "689453d499374147"),
    "mamba2-1.3b": ("fd8041cb84842bfe", "69fa3049bfa6919a",
                    "a3b49e65c3948d1a", "5234fbc5429bacf8",
                    "57c7ec9c56c164a5"),
    "whisper-base": ("af10ba48c2dce298", "ce810bb5244db6db",
                     "c274417d8f2528c7", "50115ad9cdd228c9",
                     "ee4d32257a8fcba2"),
    "chameleon-34b": ("3e0908a7650ad7ba", "9f832c8268aba7b7",
                      "df888a2a96ffbdd9", "2479002afa10c569",
                      "71a8d208ea6d9c75"),
}


def test_fingerprints_cover_every_other_model():
    assert set(PARENT_FINGERPRINTS) == set(ARCH_NAMES) - {NAME}


@pytest.mark.parametrize("arch", sorted(PARENT_FINGERPRINTS))
def test_other_models_traffic_is_bit_identical(arch):
    spec = spec_64()
    got = tuple(hashlib.sha256(scenario_matrix(spec, arch, ph).tobytes())
                .hexdigest()[:16] for ph in PHASES)
    assert got == PARENT_FINGERPRINTS[arch]
