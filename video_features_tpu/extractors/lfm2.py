"""``--feature_type lfm2``: the text stream's extractor (:mod:`.token_pages`)
over LFM2-24B-A2B (``models/lfm2.py``)."""

from .token_pages import TokenPageExtractor


class ExtractLfm2(TokenPageExtractor):
    model_name = "lfm2"
    # the share benchmark/configs/lfm2_24b_a2b_bf16.json states: layers 0-5, every expert
    random_layers, random_experts = 6, 64
