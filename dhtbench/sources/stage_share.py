"""Source kind ``stage_share``: a kernel's share of its roofline where the
kernel is a STAGE of the program and runs in part of the window only —
``{"kind": "stage_share", "stage": ..., "bytes": ...}`` is 100 × the
driver's byte count ``bytes`` (from its shape function, over the window)
/ the chip's peak HBM bytes/s / the device self time of stage ``stage``
(``sources/stage.py``, the same window).  ``sources/trace.py``'s
``hbm_share`` divides by the whole busy time, which is right for a
kernel that is the window and wrong for one that runs a few times in it.

Reads nothing where the trace names no such stage (the parent of the PR
that brought it; a window in which the kernel did not run), holds no
device plane (a CPU rehearsal) or the driver counted no bytes."""

from dhtbench.sources import stage


def read(spec: dict, ctx: dict):
    times = stage.stage_times(ctx)
    moved = ctx["values"].get(spec["bytes"])
    if not times or not times.get(spec["stage"]) or not moved \
            or ctx["peaks"] is None:
        return None
    least_s = moved / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / times[spec["stage"]]
