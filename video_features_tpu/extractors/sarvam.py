"""``--feature_type sarvam``: the text stream's extractor
(:mod:`.token_pages`) over sarvam-105b (``models/sarvam.py``)."""

from .token_pages import TokenPageExtractor


class ExtractSarvam(TokenPageExtractor):
    model_name = "sarvam"
    # the share benchmark/configs/sarvam_105b_bf16.json states
    random_layers, random_experts = 5, 16
