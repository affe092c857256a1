"""Share of its roofline that the checkpoint's device compress reaches
when a save takes several launches, in %: the least time the chip could
take for every selected leaf of a save (work.compress_work, from the
leaves' shapes), times the saves whose device stage ran in the window,
over the summed device time of all launches of the compress program in the
trace. (``compress_roofline`` counts one launch per save.)"""
import work

UNIT = "%"
LAYER = "device compress kernels (kernels/ops.py, kernels/spectral_lossy.py)"
MOVES = "train_tokens_per_s"
MODULE = r"_compress_tree_packed"


def read(rec):
    tr = rec.get("trace")
    saves = rec.get("saves_in_window")
    if tr is None or not saves:
        return None
    seconds, launches = tr.module_seconds(MODULE)
    if not launches or seconds <= 0:
        return None
    flops, nbytes = work.compress_work(rec["compress_leaf_sizes"])
    return 100.0 * work.ideal_seconds(flops, nbytes, rec["peaks"]) * saves \
        / seconds
