"""BERT model family — the north-star workload (BASELINE.json: BERT-base
MLM pretraining).

Reference parity: GluonNLP's BERTModel/BERTEncoder (gluon-nlp
scripts/bert + model zoo; the in-reference kernels it leans on are
src/operator/contrib/transformer.cu). Attr names (query/key/value/proj,
fc1/fc2, *_embed) line up with parallel.megatron_dense_rules so tp/fsdp
sharding attaches with zero model changes.
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..gluon.nn import Dense, Dropout, Embedding, LayerNorm
from ..gluon.nn.transformer import TransformerEncoder
from ..ops import nn as _opnn, tensor as _opt, init as _opinit
from ..ndarray.ndarray import NDArray

__all__ = ["BertConfig", "BertModel", "BertForMaskedLM", "BertForPretraining",
           "bert_base_config", "bert_large_config"]


class BertConfig:
    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, attention_dropout=0.1,
                 layer_norm_eps=1e-12, activation="gelu_tanh",
                 attention_impl="auto", dtype="float32"):
        self.vocab_size = vocab_size
        self.units = units
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_length = max_length
        self.type_vocab_size = type_vocab_size
        self.dropout = dropout
        self.attention_dropout = attention_dropout
        self.layer_norm_eps = layer_norm_eps
        self.activation = activation
        self.attention_impl = attention_impl
        self.dtype = dtype

    def num_params(self):
        """Analytic parameter count."""
        c = self
        embed = (c.vocab_size + c.max_length + c.type_vocab_size) * c.units \
            + 2 * c.units
        per_layer = (4 * (c.units * c.units + c.units)          # qkv + proj
                     + 2 * c.units * c.hidden_size               # fc1+fc2 w
                     + c.hidden_size + c.units                   # fc biases
                     + 4 * c.units)                              # 2 LN
        pooler = c.units * c.units + c.units
        return embed + c.num_layers * per_layer + pooler


def bert_base_config(**kw):
    return BertConfig(**kw)


def bert_large_config(**kw):
    kw.setdefault("units", 1024)
    kw.setdefault("hidden_size", 4096)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    return BertConfig(**kw)


class BertModel(HybridBlock):
    """Embeddings + transformer encoder + pooler (parity: gluon-nlp
    BERTModel)."""

    def __init__(self, config: BertConfig, use_pooler=True, **kwargs):
        super().__init__(**kwargs)
        c = self.config = config
        self.word_embed = Embedding(c.vocab_size, c.units, dtype=c.dtype)
        self.token_type_embed = Embedding(c.type_vocab_size, c.units,
                                          dtype=c.dtype)
        self.position_embed = Embedding(c.max_length, c.units, dtype=c.dtype)
        self.embed_ln = LayerNorm(epsilon=c.layer_norm_eps,
                                  in_channels=c.units)
        self.embed_dropout = Dropout(c.dropout) if c.dropout else None
        self.encoder = TransformerEncoder(
            c.num_layers, c.units, c.hidden_size, c.num_heads,
            dropout=c.dropout, attention_dropout=c.attention_dropout,
            activation=c.activation, layer_norm_eps=c.layer_norm_eps,
            attention_impl=c.attention_impl)
        self.pooler = Dense(c.units, flatten=False, activation="tanh",
                            in_units=c.units) if use_pooler else None

    def forward(self, inputs, token_types=None, valid_length=None):
        b, t = inputs.shape
        positions = _opinit.arange(0, t, dtype="int32")
        x = self.word_embed(inputs) + self.position_embed(positions)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = self.embed_ln(x)
        if self.embed_dropout is not None:
            x = self.embed_dropout(x)
        mask = None
        if valid_length is not None:
            pos = _opinit.arange(0, t, dtype="int32")
            mask = pos.reshape((1, t)) < valid_length.reshape((-1, 1))
        seq = self.encoder(x, mask)
        if self.pooler is None:
            return seq
        pooled = self.pooler(seq[:, 0])
        return seq, pooled


class _MLMHead(HybridBlock):
    """Transform + decoder (weight-tied to word embedding) + bias."""

    def __init__(self, config, word_embed, **kwargs):
        super().__init__(**kwargs)
        c = config
        self.transform = Dense(c.units, flatten=False, in_units=c.units,
                               activation=c.activation
                               if c.activation != "gelu_tanh" else None)
        self._act = c.activation
        self.transform_ln = LayerNorm(epsilon=c.layer_norm_eps,
                                      in_channels=c.units)
        # tied weights: bypass Block.__setattr__ so the embedding is NOT
        # re-registered as a child here (it would be collected — and
        # updated — twice through both paths)
        object.__setattr__(self, "_word_embed", word_embed)
        from ..gluon.parameter import Parameter
        self.decoder_bias = Parameter("decoder_bias", shape=(c.vocab_size,),
                                      init="zeros")

    def forward(self, hidden, masked_positions=None):
        if masked_positions is not None:
            # gather only masked slots: (B, M, C) — the GluonNLP approach
            hidden = _opt.take_along_axis(
                hidden, masked_positions.reshape(
                    (masked_positions.shape[0], -1, 1)), axis=1)
        h = self.transform(hidden)
        if self._act == "gelu_tanh":
            h = _opnn.gelu(h, approximate=True)
        h = self.transform_ln(h)
        w = self._word_embed.weight.data()  # (V, C) — tied
        logits = _opnn.FullyConnected(h, w, self.decoder_bias.data(),
                                      flatten=False)
        return logits


class BertForMaskedLM(HybridBlock):
    """BERT with the MLM head (parity: gluon-nlp BERTForMLM / the
    pretraining script model)."""

    def __init__(self, config: BertConfig, **kwargs):
        super().__init__(**kwargs)
        self.config = config
        self.backbone = BertModel(config, use_pooler=False)
        self.mlm = _MLMHead(config, self.backbone.word_embed)

    def forward(self, inputs, token_types=None, valid_length=None,
                masked_positions=None):
        seq = self.backbone(inputs, token_types, valid_length)
        return self.mlm(seq, masked_positions)


class BertForPretraining(HybridBlock):
    """MLM + next-sentence-prediction heads."""

    def __init__(self, config: BertConfig, **kwargs):
        super().__init__(**kwargs)
        self.config = config
        self.backbone = BertModel(config, use_pooler=True)
        self.mlm = _MLMHead(config, self.backbone.word_embed)
        self.nsp = Dense(2, flatten=False, in_units=config.units)

    def forward(self, inputs, token_types=None, valid_length=None,
                masked_positions=None):
        seq, pooled = self.backbone(inputs, token_types, valid_length)
        return self.mlm(seq, masked_positions), self.nsp(pooled)
