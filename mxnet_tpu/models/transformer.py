"""Transformer / BERT model family (flagship for BASELINE.json config 4).

The reference delegates transformers to GluonNLP built from MXNet primitives
(`src/operator/nn/` FC/layer_norm/softmax + `np_einsum_op.cc`).  Here the
same architecture is assembled from ``mxnet_tpu.gluon`` blocks, designed
TPU-first:

* attention math is einsum-form so XLA maps it onto the MXU as large batched
  matmuls (no reshape/transpose chains that break fusion);
* every parameter has a natural tensor-parallel axis; `bert_partition_rules`
  gives Megatron-style column/row sharding over a mesh axis ``tp`` —
  QKV/FFN-in kernels split on the output dim, proj/FFN-out on the input dim,
  embeddings on the vocab dim.  With batch over ``dp`` and sequence over
  ``sp``, XLA inserts the all-reduces over ICI (SURVEY.md §5.8);
* dropout draws keys from the functional RNG stream, so the whole forward
  jits into one program under ``hybridize()``.

True ring/context parallelism for very long sequences lives in
`mxnet_tpu.parallel.ring_attention` and can replace the attention core.
"""
from __future__ import annotations

import math

import numpy as onp

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import Parameter
from .. import numpy as np
from .. import numpy_extension as npx
from ..context import on_tpu
from ..parallel.mesh import PartitionSpec

__all__ = [
    "MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderLayer",
    "TransformerEncoder", "BertModel", "BertForPretraining",
    "bert_partition_rules", "bert_base", "bert_large",
]

# Sequence lengths from which `use_flash="auto"` takes the Pallas kernel
# over XLA's dense attention: the lower one when a backward pass follows
# (flash's fwd+bwd kernels against dense's joint schedule), the higher
# one forward-only (XLA's fused dense forward holds out longer).  Causal
# and non-causal share the pair.  Dense also stops fitting HBM at long T;
# flash is then the only path.  Set on jax 0.4.37 on another machine (the
# timings are in git history, 1f4c461); this machine has no number for
# either crossover — `bert_base.phase1_t128` sits below both and takes
# dense.  Re-pick them from runs of W1 (`bert_base.phase2_t512`) and W6
# (T >= 2048) and name the ledger lines here (ROADMAP S4).
FLASH_AUTO_MIN_T = 2048           # fwd-only (inference) crossover
FLASH_AUTO_MIN_T_TRAINING = 1024  # fwd+bwd crossover


def _flash_shape_ok(t):
    """The Pallas kernel's shape contract (single source for the single-
    chip policy and the sp ring's per-step check): T must be <=128 or a
    multiple of 128 (ops/pallas_kernels._resolve divisibility)."""
    return t <= 128 or t % 128 == 0


class MultiHeadAttention(HybridBlock):
    """Scaled dot-product multi-head attention.

    Shapes are (batch, seq, units) throughout; heads are split with a single
    reshape and contracted with einsum: ``BTHD,BSHD->BHTS`` then
    ``BHTS,BSHD->BTHD`` — two MXU-shaped batched matmuls per layer.
    """

    def __init__(self, units, num_heads, dropout=0.0, use_bias=True,
                 dtype="float32", use_flash="auto"):
        super().__init__()
        assert units % num_heads == 0, "num_heads must divide units"
        # Pallas flash kernel for sequences where the (T, T) score matrix
        # is the memory wall; XLA's fused dense attention is faster at
        # moderate T (see ops/pallas_kernels.py).  The kernel runs
        # key-padding (B, T) masks AND attention dropout in-kernel (fwd
        # and bwd — the recipe-realistic BERT configuration stays on the
        # fast path); only full (B, T, S) attention masks still require
        # the dense path, and T must be <=128 or a multiple of 128.  The
        # default "auto" picks flash per call once T reaches the
        # crossover (FLASH_AUTO_MIN_T*) and every constraint
        # holds; True forces it (and raises on violations), False forces
        # dense.
        # identity checks: `1 in (True, ...)` is True by equality
        if not (use_flash is True or use_flash is False or
                use_flash == "auto"):
            raise ValueError(
                f"use_flash must be True, False, or 'auto'; got "
                f"{use_flash!r}")
        self._units = units
        self._num_heads = num_heads
        self._head_dim = units // num_heads
        self._use_flash = use_flash
        self._attn_dropout_rate = dropout
        init_std = init.Normal(0.02)
        self.query = nn.Dense(units, flatten=False, use_bias=use_bias,
                              weight_initializer=init_std, dtype=dtype)
        self.key = nn.Dense(units, flatten=False, use_bias=use_bias,
                            weight_initializer=init_std, dtype=dtype)
        self.value = nn.Dense(units, flatten=False, use_bias=use_bias,
                              weight_initializer=init_std, dtype=dtype)
        self.proj = nn.Dense(units, flatten=False, use_bias=use_bias,
                             weight_initializer=init_std, dtype=dtype)
        self.attn_dropout = nn.Dropout(dropout)
        self._sp_mesh = None
        self._sp_axis = "sp"
        self._sp_batch_axis = None

    def bind_sp_mesh(self, mesh, axis_name="sp", batch_axis=None):
        """Sequence parallelism: route attention through
        `parallel.ring_attention` — the T axis of the incoming activations
        is (to be) sharded over ``mesh[axis_name]``, K/V blocks rotate on
        the ICI ring, and with flash eligible each ring step runs the
        Pallas kernel (lse-merged).  Composes with ``use_flash`` and the
        encoder-level ``remat`` boundary — the three long-context levers
        stack.
        Key-padding (B, T) masks thread through the ring (each ring step
        applies the resident K block's mask; the lse merge is
        mask-agnostic).  Attention dropout stays excluded here: per-step
        in-kernel dropout would need per-device seed offsets to
        decorrelate shards — the documented upgrade path."""
        if self._attn_dropout_rate > 0:
            raise ValueError("sequence parallelism excludes attention "
                             "dropout; set dropout=0")
        self._sp_mesh = mesh
        self._sp_axis = axis_name
        self._sp_batch_axis = batch_axis
        return self

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Megatron attention sharding: Q/K/V column-split (weight dim 0 +
        bias), the output projection row-split with a replicated bias.
        Collected by ``Block.collect_partition_rules`` BEFORE the child
        Dense blocks' generic rules, so proj gets its row split instead of
        the Dense default column."""
        return [
            (prefix + r"(query|key|value)\.weight$",
             PartitionSpec(axis_name, None)),
            (prefix + r"(query|key|value)\.bias$", PartitionSpec(axis_name)),
            (prefix + r"proj\.weight$", PartitionSpec(None, axis_name)),
            (prefix + r"proj\.bias$", PartitionSpec()),
        ]

    def _flash_now(self, t, mask):
        """Resolve the use_flash policy for this call (T is trace-static,
        so the choice bakes into the compiled program per shape).  When a
        backward pass is coming the LOWER training crossover applies
        (see the `FLASH_AUTO_MIN_T*` note)."""
        if self._use_flash == "auto":
            # is_backward_expected covers every backward-bound path:
            # eager tape (recording), train_mode, FusedTrainStep /
            # hybridize traces (explicit backward flag — traces force
            # recording off, so the tape flag can't carry it).  The one
            # misread is a train_mode() forward-only run (MC-dropout
            # style) at T in [1024, 4096), which takes flash where dense
            # fwd is ~2x faster — accepted: both are sub-4 ms, and the
            # opposite misread would cost real training throughput.
            from ..ops.invoke import is_backward_expected
            min_t = (FLASH_AUTO_MIN_T_TRAINING if is_backward_expected()
                     else FLASH_AUTO_MIN_T)
            # key-padding (B, S) masks and attention dropout both run
            # in-kernel (round 6); only a full (B, T, S) attention mask
            # forces the dense path
            mask_ok = mask is None or getattr(mask, "ndim", None) == 2
            # TPU only: elsewhere the kernel runs interpreted, orders of
            # magnitude slower than dense XLA
            return (on_tpu() and mask_ok and
                    t >= min_t and _flash_shape_ok(t))
        return bool(self._use_flash)

    def forward(self, x, mask=None):
        b, t, _ = x.shape
        h, d = self._num_heads, self._head_dim
        q = self.query(x).reshape(b, t, h, d)
        k = self.key(x).reshape(b, t, h, d)
        v = self.value(x).reshape(b, t, h, d)
        if self._sp_mesh is not None:
            if mask is not None and getattr(mask, "ndim", None) != 2:
                raise ValueError(
                    "sequence-parallel attention takes key-padding (B, T) "
                    "masks only (the mask shards and rotates with K/V)")
            from ..parallel.ring_attention import ring_attention
            # flash inside the ring: forced True honors it (and raises on
            # kernel-contract violations, same as single-chip); auto
            # requires TPU AND the per-ring-step block length (T / sp) to
            # satisfy the kernel's divisibility contract — the crossover
            # itself is considered passed (sp is chosen because T is long)
            t_local = t // self._sp_mesh.shape[self._sp_axis]
            flash = (self._use_flash is True or
                     (self._use_flash == "auto" and on_tpu() and
                      _flash_shape_ok(t_local)))
            out = ring_attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                mesh=self._sp_mesh, axis_name=self._sp_axis,
                causal=False, batch_axis=self._sp_batch_axis,
                use_flash=flash, mask=mask)
            out = out.swapaxes(1, 2).reshape(b, t, h * d)
            return self.proj(out)
        if self._flash_now(t, mask):
            if mask is not None and mask.ndim != 2:
                raise ValueError(
                    "use_flash runs key-padding (batch, seq) masks "
                    "in-kernel; full (b, t, s) attention masks take the "
                    "dense path (use_flash=False)")
            # length validation lives in the kernel (single source of
            # truth: _flash_forward's divisibility check).  Attention
            # dropout runs in-kernel, gated on train mode exactly like
            # the dense path's nn.Dropout
            from ..ops.invoke import is_training
            drop = self._attn_dropout_rate if is_training() else 0.0
            out = npx.flash_attention(q.swapaxes(1, 2), k.swapaxes(1, 2),
                                      v.swapaxes(1, 2), mask=mask,
                                      dropout=drop)
            out = out.swapaxes(1, 2).reshape(b, t, h * d)
            return self.proj(out)
        scores = np.einsum("bthd,bshd->bhts", q, k) / math.sqrt(d)
        if mask is not None:
            # mask: (b, s) valid-token mask or (b, t, s) attention mask
            if mask.ndim == 2:
                mask = mask.reshape(b, 1, 1, t)
            elif mask.ndim == 3:
                mask = mask.reshape(b, 1, t, t)
            scores = np.where(mask.astype("bool"), scores,
                              np.full_like(scores, -1e9))
        attn = npx.softmax(scores, axis=-1)
        attn = self.attn_dropout(attn)
        out = np.einsum("bhts,bshd->bthd", attn, v).reshape(b, t, h * d)
        return self.proj(out)


class PositionwiseFFN(HybridBlock):
    def __init__(self, units, hidden_size, activation="gelu", dropout=0.0,
                 dtype="float32"):
        super().__init__()
        init_std = init.Normal(0.02)
        self.ffn_1 = nn.Dense(hidden_size, flatten=False,
                              weight_initializer=init_std, dtype=dtype)
        self.act = nn.GELU() if activation == "gelu" else nn.Activation(activation)
        self.ffn_2 = nn.Dense(units, flatten=False,
                              weight_initializer=init_std, dtype=dtype)
        self.dropout = nn.Dropout(dropout)

    def forward(self, x):
        return self.dropout(self.ffn_2(self.act(self.ffn_1(x))))

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Megatron FFN sharding: ffn_1 column-split (weight dim 0 + bias),
        ffn_2 row-split with a replicated bias — the pair contracts locally
        and all-reduces once."""
        return [
            (prefix + r"ffn_1\.weight$", PartitionSpec(axis_name, None)),
            (prefix + r"ffn_1\.bias$", PartitionSpec(axis_name)),
            (prefix + r"ffn_2\.weight$", PartitionSpec(None, axis_name)),
            (prefix + r"ffn_2\.bias$", PartitionSpec()),
        ]


class TransformerEncoderLayer(HybridBlock):
    """Post-norm (BERT-style) encoder layer."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 layer_norm_eps=1e-12, dtype="float32", use_flash="auto"):
        super().__init__()
        # dropout propagates unchanged: the flash tier applies attention
        # dropout in-kernel, so use_flash + dropout>0 is a supported
        # (recipe-realistic) combination
        self.attention = MultiHeadAttention(units, num_heads,
                                            dropout=dropout, dtype=dtype,
                                            use_flash=use_flash)
        self.attn_ln = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.ffn = PositionwiseFFN(units, hidden_size, dropout=dropout,
                                   dtype=dtype)
        self.ffn_ln = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.dropout = nn.Dropout(dropout)

    def bind_sp_mesh(self, mesh, axis_name="sp", batch_axis=None):
        self.attention.bind_sp_mesh(mesh, axis_name, batch_axis)
        return self

    def forward(self, x, mask=None):
        x = self.attn_ln(x + self.dropout(self.attention(x, mask)))
        x = self.ffn_ln(x + self.ffn(x))
        return x


class TransformerEncoder(HybridBlock):
    def __init__(self, num_layers, units, hidden_size, num_heads,
                 dropout=0.0, layer_norm_eps=1e-12, dtype="float32",
                 use_flash="auto", remat=False):
        super().__init__()
        self._num_layers = num_layers
        # remat=True puts a rematerialization boundary around every layer
        # (npx.remat / jax.checkpoint): backward recomputes each layer's
        # activations from its input instead of saving them — memory per
        # layer drops from O(B*T*(U+FFN+heads*T_score)) to O(B*T*U), the
        # long-context lever that pairs with use_flash
        self._remat = remat
        for i in range(num_layers):
            setattr(self, f"layer{i}",
                    TransformerEncoderLayer(units, hidden_size, num_heads,
                                            dropout=dropout,
                                            layer_norm_eps=layer_norm_eps,
                                            dtype=dtype,
                                            use_flash=use_flash))

    def bind_sp_mesh(self, mesh, axis_name="sp", batch_axis=None):
        """Bind every layer's attention to the sp ring (see
        MultiHeadAttention.bind_sp_mesh); composes with ``remat`` — the
        checkpoint boundary wraps the ring step like any other layer."""
        for i in range(self._num_layers):
            getattr(self, f"layer{i}").bind_sp_mesh(mesh, axis_name,
                                                    batch_axis)
        return self

    def forward(self, x, mask=None):
        for i in range(self._num_layers):
            layer = getattr(self, f"layer{i}")
            if self._remat:
                x = npx.remat(layer)(x, mask)
            else:
                x = layer(x, mask)
        return x


class BertModel(HybridBlock):
    """BERT encoder: token + segment + position embeddings -> encoder ->
    (sequence output, pooled output).

    ``use_flash="auto"`` (default) picks the Pallas flash kernel at the
    measured crossovers — including with a ``valid_mask`` and with
    attention dropout, which both run in-kernel (padded variable-length
    batches never silently fall back to the dense O(T^2) path).  Note
    the auto policy reads "is a backward expected" from the tape, so
    forward-only passes that run in *train mode* (e.g. MC-dropout
    inference) at 1024 <= T < 2048 get the training tier where dense
    forward is ~2x faster — pass ``use_flash=False`` explicitly for
    that usage pattern."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 num_segments=2, dropout=0.1, layer_norm_eps=1e-12,
                 dtype="float32", use_flash="auto", remat=False):
        super().__init__()
        self._units = units
        init_std = init.Normal(0.02)
        self.word_embed = nn.Embedding(vocab_size, units,
                                       weight_initializer=init_std, dtype=dtype)
        self.segment_embed = nn.Embedding(num_segments, units,
                                          weight_initializer=init_std,
                                          dtype=dtype)
        self.position_embed = Parameter("position_embed",
                                        shape=(max_length, units),
                                        init=init_std, dtype=dtype)
        self.embed_ln = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
        self.embed_dropout = nn.Dropout(dropout)
        self.encoder = TransformerEncoder(num_layers, units, hidden_size,
                                          num_heads, dropout=dropout,
                                          layer_norm_eps=layer_norm_eps,
                                          dtype=dtype, use_flash=use_flash,
                                          remat=remat)
        self.pooler = nn.Dense(units, flatten=False, activation="tanh",
                               weight_initializer=init_std, dtype=dtype)

    def bind_sp_mesh(self, mesh, axis_name="sp", batch_axis=None):
        """The long-context recipe, one call: attention rides the sp ring
        (flash per ring step where eligible), composing with
        ``use_flash`` and ``remat`` — construct with
        ``BertModel(use_flash=..., remat=True)`` then bind.  A (B, T)
        ``valid_mask`` threads through the ring; attention dropout is
        the one exclusion (requires dropout=0 — per-device seed offsets
        are the documented upgrade path)."""
        self.encoder.bind_sp_mesh(mesh, axis_name, batch_axis)
        return self

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """Root-level params the child blocks cannot cover: the position
        embedding table is explicitly replicated (its sequence dim is not
        a tensor-parallel axis).  Everything else comes from the child
        blocks' own rules (Embedding vocab split, attention/FFN Megatron
        splits, norm replication)."""
        return [(prefix + r"position_embed$", PartitionSpec())]

    def forward(self, tokens, segments=None, valid_mask=None):
        b, t = tokens.shape
        x = self.word_embed(tokens)
        if segments is not None:
            x = x + self.segment_embed(segments)
        x = x + self.position_embed.data()[:t]
        x = self.embed_dropout(self.embed_ln(x))
        seq = self.encoder(x, valid_mask)
        pooled = self.pooler(seq[:, 0, :])
        return seq, pooled


class BertForPretraining(HybridBlock):
    """MLM + next-sentence heads over BertModel (the pretraining step of
    BASELINE.json config 4)."""

    def __init__(self, **kwargs):
        super().__init__()
        self.bert = BertModel(**kwargs)
        units = self.bert._units
        init_std = init.Normal(0.02)
        self.mlm_transform = nn.Dense(units, flatten=False, activation=None,
                                      weight_initializer=init_std)
        self.mlm_act = nn.GELU()
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        # decoder bias; the kernel is tied to the word embedding
        self.mlm_bias = Parameter("mlm_bias",
                                  shape=(self.bert.word_embed._input_dim,),
                                  init=init.Zero())
        self.nsp = nn.Dense(2, flatten=False, weight_initializer=init_std)

    def bind_sp_mesh(self, mesh, axis_name="sp", batch_axis=None):
        self.bert.bind_sp_mesh(mesh, axis_name, batch_axis)
        return self

    @staticmethod
    def partition_rules(axis_name="tp", prefix=".*"):
        """The MLM decoder bias shards over the vocab dim to match the
        tied (vocab-split) word embedding it adds onto."""
        return [(prefix + r"mlm_bias$", PartitionSpec(axis_name))]

    def forward(self, tokens, segments=None, valid_mask=None):
        seq, pooled = self.bert(tokens, segments, valid_mask)
        h = self.mlm_ln(self.mlm_act(self.mlm_transform(seq)))
        embed_w = self.bert.word_embed.weight.data()  # (vocab, units)
        mlm_logits = np.matmul(h, embed_w.T) + self.mlm_bias.data()
        nsp_logits = self.nsp(pooled)
        return mlm_logits, nsp_logits


def bert_partition_rules(tp_axis="tp"):
    """Megatron-style tensor-parallel rules for `parallel.shard_parameters`.

    Dense weights are stored (out, in) — see `gluon.nn.Dense`.  Column-split
    layers (QKV, FFN-in) shard dim 0; row-split layers (attention proj,
    FFN-out) shard dim 1; embeddings shard the vocab/hidden dim so the MLM
    matmul contracts locally and all-reduces once.
    """
    col = PartitionSpec(tp_axis, None)
    row = PartitionSpec(None, tp_axis)
    return [
        (r"attention\.(query|key|value)\.weight", col),
        (r"attention\.(query|key|value)\.bias", PartitionSpec(tp_axis)),
        (r"attention\.proj\.weight", row),
        (r"ffn\.ffn_1\.weight", col),
        (r"ffn\.ffn_1\.bias", PartitionSpec(tp_axis)),
        (r"ffn\.ffn_2\.weight", row),
        (r"word_embed\.weight", col),
        (r"mlm_bias", PartitionSpec(tp_axis)),
    ]


def bert_base(**kwargs):
    cfg = dict(vocab_size=30522, units=768, hidden_size=3072, num_layers=12,
               num_heads=12)
    cfg.update(kwargs)
    return BertModel(**cfg)


def bert_large(**kwargs):
    cfg = dict(vocab_size=30522, units=1024, hidden_size=4096, num_layers=24,
               num_heads=16)
    cfg.update(kwargs)
    return BertModel(**cfg)
