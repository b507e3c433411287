"""Costs of the fixture architecture `cls_encoder` (test_files_only.py): a
pre-LN encoder whose vector is its first token's costs what the BERT-style
encoder of the same sizes costs, so it takes that architecture's counts."""

from chipbench.architectures.bert_encoder.costs import (  # noqa: F401
    activation_bytes, dry_cut, embed_dim, flops, resident_param_bytes, weight_bytes,
)
