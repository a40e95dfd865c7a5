"""bert_base: `models.BertForPretraining` with the MLM+NSP loss under Adam in
one `FusedTrainStep`, built from bert_base.json (construction copied from
`benchmark/bert_pretrain_bench.py::build`)."""
import jax
import jax.numpy as jnp
import numpy as onp

# Model FLOPs of one training step, counted over SLOTS (padding included: the
# device computes them).  Per slot 6 * N_dense: 2 per dense weight forward and
# 4 backward, N_dense = all parameters but the three embedding tables (their
# forward is a gather) plus the tied U x V vocabulary projection, which is a
# matmul; plus 12 * L * U * T for attention's two T x T matmuls, fwd + bwd.
FLOP_CONVENTION = "2 FLOPs per multiply-add; 6*N_dense + 12*L*U*T per slot, padded slots included"


def build(cfg):
    """(model with its loss, trainer).  Parameters come from the model's own
    initializer under the seed the runner has set."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.models import BertForPretraining

    model = BertForPretraining(
        vocab_size=cfg["vocab_size"], units=cfg["hidden_size"],
        hidden_size=cfg["intermediate_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"], max_length=cfg["max_position_embeddings"],
        num_segments=cfg["type_vocab_size"], dropout=cfg["hidden_dropout_prob"],
        layer_norm_eps=cfg["layer_norm_eps"], use_flash=cfg["use_flash"])
    model.initialize()
    model.cast(cfg["dtype"])

    class PretrainLoss(HybridBlock):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, tokens, segments, labels, valid_mask):
            mlm_logits, nsp_logits = self.m(tokens, segments, valid_mask)
            logp = mx.npx.log_softmax(mlm_logits.astype("float32"), axis=-1)
            m = valid_mask.astype("float32")   # padded positions carry no loss
            mlm = -(mx.npx.pick(logp, labels, axis=-1) * m).sum() / m.sum()
            nsp = -mx.np.mean(mx.npx.log_softmax(nsp_logits.astype("float32"))[:, 0])
            return mlm + nsp

    mod = PretrainLoss(model)
    # deferred shapes: one eager forward of ONE short sequence, not the batch
    one = mx.np.zeros((1, 8), dtype="int32")
    mod(one, one, one, mx.np.ones((1, 8), dtype="int32"))
    return mod, mx.gluon.Trainer(model.collect_params(), cfg["optimizer"],
                                 dict(cfg["optimizer_params"]))


def ring_lengths(cell, chips, seed):
    """Valid lengths of every sequence of the ring, shape (ring, batch): the
    SAME evenly spaced set over [min, max] for every seed, in an order drawn
    from the seed, so that no seed gets more work than another."""
    lo, hi = cell["valid_lengths"]
    count = cell["ring"] * cell["batch"] * chips
    lens = lo + (onp.arange(count) * (hi - lo + 1)) // count
    return onp.random.default_rng(seed).permutation(lens).reshape(cell["ring"], -1)


def make_ring(cfg, cell, chips, seed, sharding):
    """`ring` batches of `batch` sequences per chip, made on the device in one
    jitted call: [((tokens, segments, labels, valid mask), VALID tokens)]."""
    from mxnet_tpu.ndarray.ndarray import NDArray
    lens = ring_lengths(cell, chips, seed)
    n, b, t, v = cell["ring"], lens.shape[1], cell["seq_len"], cfg["vocab_size"]

    def make(key, lens):
        kt, kl = jax.random.split(key)
        tokens = jax.random.randint(kt, (n, b, t), 0, v, jnp.int32)
        labels = jax.random.randint(kl, (n, b, t), 0, v, jnp.int32)
        mask = (jnp.arange(t)[None, None, :] < lens[:, :, None]).astype(jnp.int32)
        return [(tokens[i], jnp.zeros((b, t), jnp.int32), labels[i], mask[i]) for i in range(n)]

    made = jax.jit(make, out_shardings=sharding)(jax.random.key(seed), jnp.asarray(lens, jnp.int32))
    return [(tuple(NDArray(a) for a in arrays), int(lens[i].sum()))
            for i, arrays in enumerate(made)]


def flops_per_step(cfg, cell, chips, mod):
    t, sizes = cell["seq_len"], {k: int(onp.prod(p.shape)) for k, p in mod.collect_params().items()}
    n_dense = sum(n for k, n in sizes.items() if "embed" not in k.lower()) \
        + cfg["hidden_size"] * cfg["vocab_size"]
    per_slot = 6.0 * n_dense + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] * t
    return per_slot * cell["batch"] * chips * t
