"""BERT/ERNIE-style bidirectional encoder + pretraining heads.

Workload parity: SURVEY.md section 6, BERT-base Fleet and ERNIE AMP-O2.
Built on the same nn.TransformerEncoder the reference exposes
(python/paddle/nn/layer/transformer.py:404,541); ERNIE shares the
architecture (segment embeddings + MLM/NSP heads), so `ErnieModel` is the
same graph with ERNIE defaults.
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import tensor_ops as T
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.norm import LayerNorm
from ..nn.layer.transformer import TransformerEncoder, TransformerEncoderLayer
from ..ops import fused


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.1
    layer_norm_epsilon: float = 1e-12
    initializer_range: float = 0.02


def _init(cfg):
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word = Embedding(cfg.vocab_size, cfg.hidden_size,
                              weight_attr=_init(cfg))
        self.position = Embedding(cfg.max_position_embeddings, cfg.hidden_size,
                                  weight_attr=_init(cfg))
        self.token_type = Embedding(cfg.type_vocab_size, cfg.hidden_size,
                                    weight_attr=_init(cfg))
        self.layer_norm = LayerNorm(cfg.hidden_size,
                                    epsilon=cfg.layer_norm_epsilon)
        self.dropout = Dropout(cfg.dropout)

    def forward(self, input_ids, token_type_ids=None):
        import paddle_tpu as paddle

        pos = paddle.arange(input_ids.shape[1])
        x = self.word(input_ids) + self.position(pos)
        if token_type_ids is None:
            # BERT semantics: absent segment ids mean segment 0 — the
            # type-0 embedding row is still ADDED (HF/paddlenlp default
            # token_type_ids=zeros), not skipped; skipping shifts every
            # hidden state and breaks checkpoint parity
            x = x + self.token_type.weight[0]
        else:
            x = x + self.token_type(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertModel(Layer):
    def __init__(self, cfg: BertConfig | None = None, **kwargs):
        super().__init__()
        self.cfg = cfg or BertConfig(**kwargs)
        cfg = self.cfg
        self.embeddings = BertEmbeddings(cfg)
        layer = TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.dropout, activation="gelu")
        self.encoder = TransformerEncoder(layer, cfg.num_layers)
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size,
                             weight_attr=_init(cfg))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        x = self.embeddings(input_ids, token_type_ids)
        seq = self.encoder(x, src_mask=attention_mask)
        pooled = F.tanh(self.pooler(seq[:, 0]))
        return seq, pooled


class BertForPretraining(Layer):
    """MLM + NSP heads (the BERT-base pretraining objective)."""

    def __init__(self, cfg: BertConfig | None = None, **kwargs):
        super().__init__()
        self.bert = BertModel(cfg, **kwargs)
        cfg = self.bert.cfg
        self.mlm_transform = Linear(cfg.hidden_size, cfg.hidden_size,
                                    weight_attr=_init(cfg))
        self.mlm_norm = LayerNorm(cfg.hidden_size,
                                  epsilon=cfg.layer_norm_epsilon)
        self.mlm_bias = self.create_parameter(
            [cfg.vocab_size], is_bias=True)
        self.nsp = Linear(cfg.hidden_size, 2, weight_attr=_init(cfg))

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.mlm_norm(F.gelu(self.mlm_transform(seq)))
        # decoder tied to the word embedding (BERT weight tying)
        logits = T.matmul(
            h, T.transpose(self.bert.embeddings.word.weight, [1, 0]))
        logits = logits + self.mlm_bias
        return logits, self.nsp(pooled)

    def loss(self, input_ids, mlm_labels, nsp_labels, token_type_ids=None,
             ignore_index=-100):
        mlm_logits, nsp_logits = self.forward(input_ids, token_type_ids)
        mlm = fused.softmax_cross_entropy(mlm_logits, mlm_labels,
                                          ignore_index=ignore_index)
        denom = T.cast(T.sum(T.cast(mlm_labels != ignore_index, "float32")),
                       "float32")
        mlm_loss = T.sum(mlm) / T.clip(denom, min=1.0)
        nsp_loss = T.mean(fused.softmax_cross_entropy(nsp_logits, nsp_labels))
        return mlm_loss + nsp_loss


class ErnieModel(BertModel):
    """ERNIE 1.0/2.0 share BERT's graph with different defaults + data
    (entity masking lives in the data pipeline, not the model)."""

    def __init__(self, cfg: BertConfig | None = None, **kwargs):
        if cfg is None:
            defaults = dict(vocab_size=18000, type_vocab_size=4)
            defaults.update(kwargs)
            cfg = BertConfig(**defaults)
        super().__init__(cfg)
