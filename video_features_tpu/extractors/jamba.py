"""``--feature_type jamba``: the text stream's extractor (:mod:`.token_pages`)
over AI21-Jamba2-3B (``models/jamba.py``)."""

from .token_pages import TokenPageExtractor


class ExtractJamba(TokenPageExtractor):
    model_name = "jamba"
    # the whole model, as benchmark/configs/jamba2_3b_bf16.json states it; no experts
    random_layers, random_experts = 28, 0
