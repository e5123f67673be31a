"""Per-model extraction pipelines sharing one skeleton.

The reference duplicates the decode→transform→batch→forward→collect→output skeleton
in every ``extract_<name>.py`` (SURVEY.md §1); here it is factored once into
:class:`base.Extractor` with per-model subclasses that supply the host transform,
the window plan, and the jitted device step.
"""

def get_extractor(cfg):
    """Instantiate the extractor for ``cfg.feature_type`` (lazy imports keep
    startup light, mirroring the reference's in-branch imports ``main.py:15-33``)."""
    ft = cfg.feature_type
    if ft == "resnet50":
        from .resnet import ExtractResNet50
        return ExtractResNet50(cfg)
    if ft == "r21d_rgb":
        from .r21d import ExtractR21D
        return ExtractR21D(cfg)
    if ft == "i3d":
        from .i3d import ExtractI3D
        return ExtractI3D(cfg)
    if ft in ("raft", "pwc"):
        from .flow import ExtractFlow
        return ExtractFlow(cfg)
    if ft == "vggish":
        from .vggish import ExtractVGGish
        return ExtractVGGish(cfg)
    if ft == "laguna":
        from .laguna import ExtractLaguna
        return ExtractLaguna(cfg)
    if ft == "sarvam":
        from .sarvam import ExtractSarvam
        return ExtractSarvam(cfg)
    if ft == "qwen3_next":
        from .qwen3_next import ExtractQwen3Next
        return ExtractQwen3Next(cfg)
    if ft == "jamba":
        from .jamba import ExtractJamba
        return ExtractJamba(cfg)
    if ft == "lfm2":
        from .lfm2 import ExtractLfm2
        return ExtractLfm2(cfg)
    raise ValueError(f"unknown feature_type: {ft}")
