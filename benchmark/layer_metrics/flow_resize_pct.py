"""PWC-Net's two bilinear resizes as a share of the device's busy time: the
self time of the traced operations under the scopes ``pwc/resize_in`` (the
frames to the /64 grid) and ``pwc/resize_out`` (the flow back), over
``busy_s``. It follows the work by its scope, whatever fusions the compiler
makes of it (``_spans`` says where the scope is read)."""

from ._spans import scope_seconds

SCOPES = ("pwc/resize_in", "pwc/resize_out")


def read(trace, stats, facts):
    if not trace.get("busy_s"):
        return None
    seconds = scope_seconds(trace, SCOPES)
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s"]
