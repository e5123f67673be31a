"""``--feature_type laguna``: the text stream's extractor
(:mod:`.token_pages`) over Laguna-S-2.1 (``models/laguna.py``)."""

from .token_pages import ATTENTION_BLOCK, SEGMENT_TOKENS_MIN, TokenPageExtractor  # noqa: F401 — the page's constants, under the type's name too


class ExtractLaguna(TokenPageExtractor):
    model_name = "laguna"
    # the share benchmark/configs/laguna_s21_bf16.json states
    random_layers, random_experts = 5, 64
