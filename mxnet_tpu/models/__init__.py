"""Flagship model families for the TPU build.

The reference keeps its CNN zoo in `python/mxnet/gluon/model_zoo/vision/`
(mirrored here under ``mxnet_tpu.gluon.model_zoo``) and its transformer stack
in GluonNLP (BASELINE.json config 4: BERT-base pretraining).  This package
holds the transformer/BERT family, written mesh-aware from the start:
parameters carry partition rules so the same Block runs single-chip or
dp/tp/sp-sharded over a `jax.sharding.Mesh` unchanged.
"""
from .rnn_lm import RNNModel, rnn_lm_partition_rules
from .decoder import (
    RMSNorm, GroupedQueryAttention, KimiDeltaAttention, LatentAttention,
    SwiGLU, HyperConnection, DecoderLayer, MultiTokenPrediction, DecoderLM,
    CausalLMLoss,
)
from .transformer import (
    MultiHeadAttention,
    PositionwiseFFN,
    TransformerEncoderLayer,
    TransformerEncoder,
    BertModel,
    BertForPretraining,
    bert_partition_rules,
    bert_base,
    bert_large,
)

__all__ = [
    "RNNModel", "rnn_lm_partition_rules",
    "MultiHeadAttention", "PositionwiseFFN", "TransformerEncoderLayer",
    "TransformerEncoder", "BertModel", "BertForPretraining",
    "bert_partition_rules", "bert_base", "bert_large",
    "RMSNorm", "GroupedQueryAttention", "KimiDeltaAttention",
    "LatentAttention", "SwiGLU", "HyperConnection", "DecoderLayer",
    "MultiTokenPrediction", "DecoderLM", "CausalLMLoss",
]
