"""PWC-Net's two bilinear resizes as a share of the device's busy time: the
self time of the traced operations under the scopes ``pwc/resize_in`` (the
frames to the /64 grid) and ``pwc/resize_out`` (the flow back), over
``busy_s``. It follows the work by its scope, whatever fusions the compiler
makes of it (``_spans`` says where the scope is read). A slice in which the
flow net ran (``pwc/`` scopes with self time) and no operation stands under
either resize reads 0.0: the compiler fused the resizes away, or a change
made them free. Without ``pwc/`` scopes there is nothing to read."""

from ._spans import scope_seconds

SCOPES = ("pwc/resize_in", "pwc/resize_out")
FLOW_NET = ("pwc/",)


def read(trace, stats, facts):
    if not trace.get("busy_s"):
        return None
    seconds = scope_seconds(trace, SCOPES)
    if seconds is None or (not seconds and not scope_seconds(trace, FLOW_NET)):
        return None
    return 100.0 * seconds / trace["busy_s"]
