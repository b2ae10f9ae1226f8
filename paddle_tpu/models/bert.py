"""BERT/ERNIE-style encoder — BASELINE config 3 flagship.

Reference equivalents: PaddleNLP BERT on top of the reference transformer
stack (python/paddle/nn/layer/transformer.py) with fused attention
(operators/fused/multihead_matmul_op, fused_embedding_eltwise_layernorm).
Built on paddle_tpu.nn; runs in eager mode and jits cleanly (whole
pretrain step = one XLA computation, bf16 on the MXU via amp).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .. import nn
from ..nn import functional as F


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12

    @classmethod
    def base(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, intermediate_size=128,
                   max_position_embeddings=128)


class BertEmbeddings(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        from ..fluid.param_attr import ParamAttr
        attr = lambda: ParamAttr(initializer=nn.initializer.Normal(
            0.0, cfg.initializer_range))
        self.word_embeddings = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                            weight_attr=attr())
        self.position_embeddings = nn.Embedding(
            cfg.max_position_embeddings, cfg.hidden_size, weight_attr=attr())
        self.token_type_embeddings = nn.Embedding(
            cfg.type_vocab_size, cfg.hidden_size, weight_attr=attr())
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps)
        self.dropout = nn.Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        from .. import tensor as T
        seq_len = input_ids.shape[1]
        if position_ids is None:
            position_ids = T.arange(0, seq_len, 1, dtype="int64")
            position_ids = T.expand(T.unsqueeze(position_ids, 0),
                                    [input_ids.shape[0], seq_len])
        if token_type_ids is None:
            token_type_ids = T.zeros_like(input_ids)
        emb = T.add(
            T.add(self.word_embeddings(input_ids),
                  self.position_embeddings(position_ids)),
            self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(emb))


class BertPooler(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.dense = nn.Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, hidden):
        from .. import tensor as T
        first = T.slice(hidden, [1], [0], [1])
        first = T.squeeze(first, [1])
        return F.tanh(self.dense(first))


class BertModel(nn.Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = nn.TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_attention_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation=cfg.hidden_act,
            attn_dropout=cfg.attention_probs_dropout_prob,
            act_dropout=0.0)
        self.encoder = nn.TransformerEncoder(enc_layer,
                                             cfg.num_hidden_layers)
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        from .. import tensor as T
        if attention_mask is not None and len(attention_mask.shape) == 2:
            # (B, S) 1/0 -> additive (B, 1, 1, S)
            m = T.cast(attention_mask, "float32")
            m = T.unsqueeze(T.unsqueeze(m, 1), 1)
            # keep=1 -> 0, pad=0 -> -1e9 : additive mask = (m - 1) * 1e9
            attention_mask = T.scale(m, scale=1e9, bias=-1.0,
                                     bias_after_scale=False)
        emb = self.embeddings(input_ids, token_type_ids, position_ids)
        seq_out = self.encoder(emb, attention_mask)
        pooled = self.pooler(seq_out)
        return seq_out, pooled


class BertPretrainingHeads(nn.Layer):
    def __init__(self, cfg: BertConfig, embedding_weights=None):
        super().__init__()
        self.transform = nn.Linear(cfg.hidden_size, cfg.hidden_size)
        self.act = getattr(F, cfg.hidden_act)
        self.layer_norm = nn.LayerNorm(cfg.hidden_size,
                                       epsilon=cfg.layer_norm_eps)
        self.decoder_weight = embedding_weights  # tied to word embeddings
        self.decoder_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self.seq_relationship = nn.Linear(cfg.hidden_size, 2)

    def forward(self, sequence_output, pooled_output,
                masked_positions=None):
        from .. import tensor as T
        if masked_positions is not None:
            # gather the masked rows BEFORE the vocab projection
            # (MLPerf-BERT / PaddleNLP practice): the [B*S, V] logits
            # shrink to [B*P, V] — the head's FLOPs and HBM traffic drop
            # by S/P (~7x at 15% masking)
            B, S = sequence_output.shape[0], sequence_output.shape[1]
            H = sequence_output.shape[2]
            flat = T.reshape(sequence_output, [-1, H])
            base = T.reshape(
                T.arange(0, B * S, S, dtype="int64"), [B, 1])
            idx = T.add(masked_positions, base)
            sequence_output = T.gather(flat, T.reshape(idx, [-1]))
        h = self.layer_norm(self.act(self.transform(sequence_output)))
        # tied softmax: logits = h @ word_embeddings^T
        logits = T.matmul(h, self.decoder_weight, transpose_y=True)
        logits = T.add(logits, self.decoder_bias)
        nsp = self.seq_relationship(pooled_output)
        return logits, nsp


class BertForPretraining(nn.Layer):
    """MLM + NSP pretraining objective (config 3)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.bert = BertModel(cfg)
        self.cls = BertPretrainingHeads(
            cfg, self.bert.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_positions=None):
        """masked_positions [B, P] selects the MLM prediction rows; the
        logits come back [B*P, V] (flattened) instead of [B, S, V], and
        loss() then takes labels [B, P]."""
        seq_out, pooled = self.bert(input_ids, token_type_ids,
                                    attention_mask=attention_mask)
        return self.cls(seq_out, pooled, masked_positions)

    def loss(self, prediction_logits, nsp_logits, masked_lm_labels,
             next_sentence_labels, ignore_index=-100):
        """Mean MLM xent over non-ignored positions + NSP xent."""
        from .. import tensor as T
        vocab = prediction_logits.shape[-1]
        logits2d = T.reshape(prediction_logits, [-1, vocab])
        labels = T.reshape(masked_lm_labels, [-1, 1])
        per_tok = F.softmax_with_cross_entropy(
            logits2d, labels, ignore_index=ignore_index)
        mask = T.cast(T.not_equal(
            labels, T.full_like(labels, ignore_index)), "float32")
        denom = T.clip(T.sum(mask), min=1.0)
        mlm = T.divide(T.sum(T.multiply(per_tok, mask)), denom)
        nsp = F.cross_entropy(nsp_logits, next_sentence_labels)
        return T.add(mlm, T.reshape(nsp, [1]))
