"""``--feature_type qwen3_next``: the text stream's extractor
(:mod:`.token_pages`) over Qwen3-Next-80B-A3B (``models/qwen3_next.py``)."""

from .token_pages import TokenPageExtractor


class ExtractQwen3Next(TokenPageExtractor):
    model_name = "qwen3_next"
    # the share benchmark/configs/qwen3_next_80b_bf16.json states
    random_layers, random_experts = 4, 128
