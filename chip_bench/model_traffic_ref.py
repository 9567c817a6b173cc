"""Plain reference of the Moonlight-16B-A3B ``train.fwd`` traffic matrix.

The configuration ``soc256-moonlight16b-train`` runs the design search on
the traffic the program derives from the model (``repro.workloads``). This
module derives the same matrix again, from the published model and the
tile spec alone: NumPy float64, nothing of the program imported. The cell's
check compares the window's objective rows and first front with
``reference.py`` on the matrix the program built; comparing that matrix
with this one at the cell's spec closes the chain at the timed size
(``tests/test_model_traffic_ref.py``).

The model is the published ``config.json`` (``PUBLISHED``, at ``SOURCE``).
The deployment and the accounting are the program's stated assumptions,
written out again here:

* tiles: CPUs ``[0, C)``, LLC banks ``[C, C+M)``, GPUs ``[C+M, N)``; CPU 0
  is the master host core;
* mesh ``(data, model)``: the model axis is the largest divisor of the GPU
  count not above ``min(8, heads)``; shard ``(i, j)`` runs on GPU
  ``C + M + i*model + j`` and keeps its parameters in LLC bank
  ``C + (i*model + j) mod M``; experts ride the model axis;
* the ``train_4k`` shape: 4096 tokens a sequence, 256 sequences a step,
  each data replica one 256/data share of them; bf16 activations and
  weights, int32 tokens;
* a ring collective sends half of each participant's bytes to each ring
  neighbour (with two participants both halves go to the one neighbour):
  an all-reduce 2(k-1)/k of the buffer, an all-gather (k-1)/k of the
  gathered buffer;
* per layer one tensor-parallel all-reduce of the attention output, and on
  the dense layer a second one of the MLP output;
* MoE dispatch and combine on every MoE layer: each token sends its
  activation to each of its 6 routed experts and gets it back, routing is
  balanced, so (k-1)/k of that leaves the shard, spread evenly over the
  other k-1 shards of the model group; shared experts run where the token
  is (no exchange);
* FSDP: each data group all-gathers its model column's parameters; each
  shard reads its parameter shard from its home bank and spills a quarter
  of one activation buffer per layer there; a read moves 1/4 of its bytes
  towards the bank (requests) and all of them back, a write the reverse;
* the master core sends each GPU its tokens and gets a tenth back, reads
  the whole batch out of the LLC banks in equal parts; every other CPU
  exchanges 2 % of the batch's bytes, spread over the banks, as
  background control.

The matrix has a zero diagonal and sums to the phase's intensity, 0.50.
"""

from __future__ import annotations

import numpy as np

SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")

#: the published config.json of Moonlight-16B-A3B (model_type deepseek_v3)
PUBLISHED = {
    "attention_bias": False,
    "ep_size": 1,
    "first_k_dense_replace": 1,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 11264,
    "kv_lora_rank": 512,
    "max_position_embeddings": 8192,
    "model_type": "deepseek_v3",
    "moe_intermediate_size": 1408,
    "moe_layer_freq": 1,
    "n_group": 1,
    "n_routed_experts": 64,
    "n_shared_experts": 2,
    "norm_topk_prob": True,
    "num_attention_heads": 16,
    "num_experts_per_tok": 6,
    "num_hidden_layers": 27,
    "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0,
    "q_lora_rank": None,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05,
    "rope_theta": 50000,
    "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid",
    "seq_aux": True,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "v_head_dim": 128,
    "vocab_size": 163840,
}

SEQ_LEN, GLOBAL_BATCH = 4096, 256       # train_4k
BF16, INT32 = 2.0, 4.0
TP_CAP = 8
INTENSITY = 0.50                        # train.fwd


def layer_params(c: dict = PUBLISHED) -> dict:
    """Parameters of one layer's parts. The router's score-correction bias
    (64 numbers a MoE layer) is left out, here and in the program."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    r, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    expert = 3 * d * c["moe_intermediate_size"]     # gate, up, down
    return {
        "attention": (d * h * (nope + rope)         # q_proj (no q_lora)
                      + d * (r + rope) + r          # kv_a_proj, kv_a norm
                      + r * h * (nope + v)          # kv_b_proj
                      + h * v * d),                 # o_proj
        "norms": 2 * d,
        "dense_mlp": 3 * d * c["intermediate_size"],
        "router": d * c["n_routed_experts"],
        "expert": expert,
    }


def param_count(c: dict = PUBLISHED) -> int:
    p = layer_params(c)
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    vocab = 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    dense = p["attention"] + p["norms"] + p["dense_mlp"]
    moe = (p["attention"] + p["norms"] + p["router"]
           + (c["n_routed_experts"] + c["n_shared_experts"]) * p["expert"])
    return vocab + n_dense * dense + n_moe * moe


def active_param_count(c: dict = PUBLISHED) -> int:
    p = layer_params(c)
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    vocab = 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]
    dense = p["attention"] + p["norms"] + p["dense_mlp"]
    moe = (p["attention"] + p["norms"] + p["router"]
           + (c["num_experts_per_tok"] + c["n_shared_experts"])
           * p["expert"])
    return vocab + n_dense * dense + n_moe * moe


def placement(spec: dict, heads: int) -> tuple[np.ndarray, np.ndarray]:
    """(gpu, home): the (data, model) arrays of each shard's GPU tile and
    home LLC bank."""
    C, M, G = spec["n_cpu"], spec["n_llc"], spec["n_gpu"]
    model = max(k for k in range(1, min(TP_CAP, heads) + 1) if G % k == 0)
    shard = np.arange(G).reshape(G // model, model)
    return C + M + shard, C + shard % M


def _ring(f: np.ndarray, ids, per_neighbour: float) -> None:
    """Each participant sends ``per_neighbour`` bytes to each of its two
    ring neighbours."""
    k = len(ids)
    if k < 2:
        return
    for i, a in enumerate(ids):
        f[a, ids[(i + 1) % k]] += per_neighbour
        f[a, ids[(i - 1) % k]] += per_neighbour


def _home(f, a, bank, read=0.0, write=0.0) -> None:
    f[a, bank] += 0.25 * read + write
    f[bank, a] += read + 0.25 * write


def train_fwd(spec: dict, c: dict = PUBLISHED) -> np.ndarray:
    """The (N, N) ``train.fwd`` flit-rate matrix of the model on ``spec``."""
    C, M, G = spec["n_cpu"], spec["n_llc"], spec["n_gpu"]
    n = C + M + G
    gpu, home = placement(spec, c["num_attention_heads"])
    data, model = gpu.shape
    d = c["hidden_size"]
    layers = c["num_hidden_layers"]
    n_dense = c["first_k_dense_replace"]
    P = float(param_count(c))

    toks = GLOBAL_BATCH * SEQ_LEN / data          # tokens a shard holds
    act = toks * d * BF16                          # one activation buffer
    allreduces = layers + n_dense                  # attention + dense MLP
    dispatch = (2.0 * (layers - n_dense) * toks * c["num_experts_per_tok"]
                * d * BF16 * (model - 1) / model)  # leaves each shard

    f = np.zeros((n, n))
    for row in gpu:                                # model groups
        _ring(f, list(row), (model - 1) / model * allreduces * act)
        if model > 1:
            block = np.ix_(row, row)
            f[block] += (dispatch / (model - 1)) * (1 - np.eye(model))
    for col in gpu.T:                              # data groups
        _ring(f, list(col), (data - 1) / (2.0 * data) * (P / model * BF16))
    for g, bank in zip(gpu.ravel(), home.ravel()):
        _home(f, g, bank, read=P / (data * model) * BF16,
              write=0.25 * layers * act)

    tokens_in = toks * INT32                       # per GPU
    f[0, gpu.ravel()] += tokens_in
    f[gpu.ravel(), 0] += 0.10 * tokens_in
    batch_in = tokens_in * G
    banks = np.arange(C, C + M)
    for bank in banks:
        _home(f, 0, bank, read=batch_in / M)
    bg = 0.02 * batch_in / M
    for cpu in range(1, C):
        f[cpu, banks] += 0.25 * bg
        f[banks, cpu] += bg

    np.fill_diagonal(f, 0.0)
    return f / f.sum() * INTENSITY


def rel_gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest gap of an entry relative to the entry (infinite where one
    matrix has traffic and the other none)."""
    got = np.asarray(got, np.float64)
    if got.shape != want.shape or np.any((got != 0) != (want != 0)):
        return float("inf")
    nz = want != 0
    return float(np.max(np.abs(got[nz] - want[nz]) / np.abs(want[nz])))
