"""mellum2_12b_a2p5b: `models.DecoderLM` (grouped-query flash attention, a
1024-key window in three layers of four, YaRN-scaled full attention in the
fourth; a softmax top-8 router over 64 experts of which this chip holds 16)
with next-token cross-entropy under AdamW in one `FusedTrainStep`, built
from mellum2_12b_a2p5b.json: one chip's share of an ep4 group.

The weights and every ring batch come from the file's `weights_seed`, NOT from
the runner's `--seed`, and the ring's order is fixed: how many routed rows land
on the held experts follows the router's weights, which training moves step by
step, and the batch, and the routed layer's loops run one trip per live block
of rows, so a cell whose weights, batches or order of batches followed the seed
would do another amount of work per seed (PERF.md, section 6).  The seed draws
the order of the sequences inside batches 1-3: each step then sees the same
tokens, routes the same rows and trains the same loss, and the first warm-up
loss is one number for every seed.
"""
import jax
import jax.numpy as jnp
import numpy

# Model FLOPs of one training step (2 per multiply-add, backward = 2 x
# forward, nothing recomputed counted).  Per token 6 * N_dense, N_dense =
# every matrix a token passes whole (attention's four, the router, the head
# over the vocabulary slice; the embedding is a gather); 6 * 3*U*F per ROUTED
# ROW on a held expert, rows as the program's own counter read them on its
# last step (the balanced quarter, tokens * top_k * held / routed, where no
# counter is there); attention's two T x T matmuls over the visible band only
# (a window layer: min(t+1, window) keys for query t), 12 * heads * head_dim
# per visible score.
FLOP_CONVENTION = ("2 FLOPs per multiply-add; 6*N_dense per token + 6*3*U*F per routed "
                   "row on a held expert (program counter) + 12*H*D per visible score")


def build(cfg):
    """(model with its loss, trainer); weights from `weights_seed`."""
    import mxnet_tpu as mx
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import CausalLMLoss, DecoderLM

    with telemetry.span("decoder.build", layers=cfg["num_hidden_layers"]):
        mx.random.seed(cfg["weights_seed"])     # not the runner's seed
        model = DecoderLM(
            vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
            layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rope_parameters=cfg["rope_parameters"], window=cfg["sliding_window"],
            expert_hidden=cfg["moe_intermediate_size"],
            num_experts=cfg["num_experts_routed"],
            top_k=cfg["num_experts_per_tok"], experts_held=cfg["num_experts"],
            ep_rank=cfg["ep_rank"], epsilon=cfg["rms_norm_eps"],
            dtype=cfg["dtype"], remat=cfg["remat"])
        model.initialize()
    return CausalLMLoss(model), mx.gluon.Trainer(
        model.collect_params(), cfg["optimizer"], dict(cfg["optimizer_params"]))


def make_ring(cfg, cell, chips, seed, sharding):
    """`ring` batches of `batch` sequences of `seq_len` ids, uniform over the
    vocabulary slice, made on the device: [((ids,), tokens)].  Batch 0 (the
    warm-up batch, whose first loss `correct` checks against the reference's)
    from `weights_seed`, the others from `weights_seed + 1`, each with its
    sequences in an order drawn from `--seed`."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    n, shape = cell["ring"], (cell["batch"] * chips, cell["seq_len"])

    def make(first, rest, orders):
        made = [jax.random.randint(k, shape, 0, cfg["vocab_size"], jnp.int32)
                for k in [first] + list(jax.random.split(rest, n - 1))]
        return made[:1] + [ids[order] for ids, order in zip(made[1:], orders)]

    rng = numpy.random.default_rng(seed)
    orders = numpy.stack([rng.permutation(shape[0]) for _ in range(n - 1)])
    made = jax.jit(make, out_shardings=sharding)(
        jax.random.key(cfg["weights_seed"]), jax.random.key(cfg["weights_seed"] + 1), orders)
    return [((NDArray(ids),), shape[0] * shape[1]) for ids in made]


def visible_scores(t, window):
    """Scores a causal sequence of t queries computes: sum of min(q+1, window)."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_windows(cfg):
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def attention_flops(cfg, cell, chips):
    """Forward + backward FLOPs of the step's attention cores over the band:
    QK^T and PV forward (4*D a score), dV, dP, dQ, dK backward (8*D)."""
    seqs = cell["batch"] * chips
    return sum(12.0 * cfg["num_attention_heads"] * cfg["head_dim"] * seqs
               * visible_scores(cell["seq_len"], w) for w in layer_windows(cfg))


def attention_bytes(cfg, cell, chips):
    """HBM bytes the three flash kernels of every layer must move once: q, k,
    v in and o out forward; q, k, v, o, do in and dq, dk, dv out backward."""
    t, d, b = cell["seq_len"], cfg["head_dim"], cell["batch"] * chips
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = 2 * b * t * d * ((2 * h + 2 * hkv) + (4 * h + 4 * hkv))
    return float(per_layer * cfg["num_hidden_layers"])


def expert_flops(cfg, rows):
    """6 * 3*U*F for every routed row on a held expert."""
    return 18.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * rows


def expert_bytes(cfg, rows):
    """The held experts' three matrices read forward and twice backward and
    their gradients written; each row's input, two hidden rows and output
    read or written in each of the three passes."""
    u, f, layers = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_hidden_layers"]
    weights = 4 * layers * cfg["num_experts"] * 3 * u * f * 2
    return float(weights + 3 * rows * (2 * u + 3 * f) * 2)


def balanced_rows(cfg, cell, chips):
    tokens = cell["batch"] * chips * cell["seq_len"]
    return tokens * cfg["num_experts_per_tok"] * cfg["num_hidden_layers"] \
        * cfg["num_experts"] // cfg["num_experts_routed"]


def routed_rows(cfg, cell, chips):
    """Rows on held experts over all layers in the last step, from the
    program's counters; the balanced quarter where they read nothing."""
    from chipbench import layer_work
    return layer_work.routed_rows() or balanced_rows(cfg, cell, chips)


def dense_flops(cfg, cell, chips):
    u, d = cfg["hidden_size"], cfg["head_dim"]
    per_layer = 2 * u * cfg["num_attention_heads"] * d \
        + 2 * u * cfg["num_key_value_heads"] * d + u * cfg["num_experts_routed"]
    n_dense = cfg["num_hidden_layers"] * per_layer + u * cfg["vocab_size"]
    return 6.0 * n_dense * cell["batch"] * chips * cell["seq_len"]


def flops_per_step(cfg, cell, chips, mod):
    return dense_flops(cfg, cell, chips) + attention_flops(cfg, cell, chips) \
        + expert_flops(cfg, routed_rows(cfg, cell, chips))
