"""Oracle independence: the torch mirrors must not share spec tables with the
Flax models, and both sides must match shapes known from real checkpoints.

Round-1 review finding: the parity oracles imported I3D_STEM / _conv_shapes /
pwc_conv_shapes / r21d_conv_shapes from the Flax models, so a wrong channel
count produced identical wrong architectures on both sides and parity still
passed. Now the mirror tables are transcribed independently from the reference
source; these tests (a) forbid re-introducing the import, (b) cross-check the
two independently-authored tables against each other, and (c) anchor both to
hard-coded shapes that real pretrained checkpoints are known to have.
"""

import os



def test_mirrors_do_not_import_flax_specs():
    import ast

    src_path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                            "tools", "torch_mirrors.py")
    with open(src_path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            assert not (node.module or "").startswith("video_features_tpu"), node.module
        elif isinstance(node, ast.Import):
            for alias in node.names:
                assert not alias.name.startswith("video_features_tpu"), alias.name


def test_i3d_tables_agree():
    from tools.torch_mirrors import I3D_LAYERS

    from video_features_tpu.models.i3d import I3D_STEM

    assert tuple(I3D_LAYERS) == tuple(I3D_STEM)


def test_raft_tables_agree():
    from tools.torch_mirrors import raft_conv_shapes

    from video_features_tpu.models.raft import _conv_shapes

    assert raft_conv_shapes() == _conv_shapes()


def test_pwc_tables_agree():
    from tools import torch_mirrors as tm

    from video_features_tpu.models import pwc as flax_pwc

    assert tm.pwc_conv_shapes() == flax_pwc.pwc_conv_shapes()
    assert tm.LEVEL_NAMES == flax_pwc.LEVEL_NAMES
    assert tm.DEC_BACKWARD == flax_pwc.DEC_BACKWARD


def test_r21d_tables_agree():
    from tools.torch_mirrors import r21d_conv_shapes

    from video_features_tpu.models.r21d import r21d_conv_shapes as flax_shapes

    assert r21d_conv_shapes() == flax_shapes()


# ---------------------------------------------------------------------------
# Anchors: shapes a REAL pretrained checkpoint is known to have (transcribed
# from torchvision r2plus1d_18 / RAFT-sintel / I3D-Kinetics / PWC state_dicts).
# These catch the case where both independently-written tables err identically.
# ---------------------------------------------------------------------------

R21D_KNOWN = {
    # torchvision r2plus1d_18: block-level midplanes — (inplanes, planes) once
    # per block, shared by conv1 AND conv2 (a round-1 review finding)
    "layer2.0.conv1.0.0.weight": (230, 64, 1, 3, 3),
    "layer2.0.conv2.0.0.weight": (230, 128, 1, 3, 3),
    "layer3.0.conv2.0.0.weight": (460, 256, 1, 3, 3),
    "layer4.0.conv2.0.0.weight": (921, 512, 1, 3, 3),
    "layer1.0.conv1.0.0.weight": (144, 64, 1, 3, 3),
    "stem.0.weight": (45, 3, 1, 7, 7),
    "fc.weight": (400, 512),
}

RAFT_KNOWN = {
    "fnet.conv2.weight": (256, 128, 1, 1),
    "cnet.conv2.weight": (256, 128, 1, 1),
    "update_block.encoder.convc1.weight": (256, 324, 1, 1),
    "update_block.encoder.conv.weight": (126, 256, 3, 3),
    "update_block.gru.convz1.weight": (128, 384, 1, 5),
    "update_block.mask.2.weight": (576, 256, 1, 1),
}

I3D_KNOWN = {
    "mixed_4f.branch_1.0.conv3d.weight": (160, 528, 1, 1, 1),
    "mixed_5c.branch_0.conv3d.weight": (384, 832, 1, 1, 1),
    "conv3d_0c_1x1.conv3d.weight": (400, 1024, 1, 1, 1),
}

PWC_KNOWN = {
    "moduleTwo.moduleOne.0.weight": (128, 117, 3, 3),
    "moduleSix.moduleOne.0.weight": (128, 81, 3, 3),
    "moduleRefiner.moduleMain.0.weight": (128, 565, 3, 3),
    "moduleThr.moduleUpfeat.weight": (181 + 448, 2, 4, 4),
}


def test_r21d_known_checkpoint_shapes():
    from tools.torch_mirrors import r21d_random_state_dict

    sd = r21d_random_state_dict()
    for name, shape in R21D_KNOWN.items():
        assert tuple(sd[name].shape) == shape, name


def test_raft_known_checkpoint_shapes():
    from tools.torch_mirrors import raft_random_state_dict

    sd = raft_random_state_dict()
    for name, shape in RAFT_KNOWN.items():
        assert tuple(sd[name].shape) == shape, name


def test_i3d_known_checkpoint_shapes():
    from tools.torch_mirrors import i3d_random_state_dict

    sd = i3d_random_state_dict("rgb")
    for name, shape in I3D_KNOWN.items():
        assert tuple(sd[name].shape) == shape, name
    # flow I3D differs only in the stem input channels
    assert tuple(i3d_random_state_dict("flow")["conv3d_1a_7x7.conv3d.weight"].shape) == (
        64, 2, 7, 7, 7,
    )


def test_pwc_known_checkpoint_shapes():
    from tools.torch_mirrors import pwc_random_state_dict

    sd = pwc_random_state_dict()
    for name, shape in PWC_KNOWN.items():
        assert tuple(sd[name].shape) == shape, name


def test_flax_params_match_known_shapes():
    """The Flax models themselves (via converted random torch weights) must
    carry the same known-checkpoint geometry — anchoring the framework side,
    not just the mirrors."""
    import numpy as np

    from tools.torch_mirrors import r21d_random_state_dict

    from video_features_tpu.weights.convert_torch import convert_r21d

    import jax

    params = convert_r21d(r21d_random_state_dict())
    # spatial conv of layer2.0's Conv2Plus1D #2: HWIO (1, 3, 3, 128, 230) in Flax
    shapes = {tuple(np.shape(l)) for l in jax.tree_util.tree_leaves(params)}
    assert (1, 3, 3, 128, 230) in shapes
    assert (3, 1, 1, 230, 128) in shapes  # its temporal half
    assert (1, 3, 3, 64, 230) in shapes   # layer2.0.conv1 spatial half
