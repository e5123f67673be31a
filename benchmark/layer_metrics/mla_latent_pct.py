"""What latent attention puts around its core: the share of the device's busy
time under ``…/attn/latent`` (the projection down to the 512-wide latent and
the shared rope key, and the latent's norm), ``…/attn/up`` (keys and values
projected up from the latent) and ``…/attn/rope`` (the rotation of a quarter
of every query head and of the one shared key)."""

from ._laguna import busy_share

SCOPES = ("/attn/latent", "/attn/up", "/attn/rope")


def read(trace, stats, facts):
    return busy_share(trace, SCOPES)
