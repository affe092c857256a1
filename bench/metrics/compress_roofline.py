"""Share of its roofline that the checkpoint's device compress program
reaches, in %: the least time the chip could take for the stage's work
(work.compress_work, from the selected leaves' shapes), over the summed
device time of that program's launches in the trace."""
import work

UNIT = "%"
LAYER = "device compress kernels (kernels/ops.py, kernels/spectral_lossy.py)"
MOVES = "train_tokens_per_s"
MODULE = r"_compress_tree_packed"


def read(rec):
    tr = rec.get("trace")
    if tr is None:
        return None
    seconds, launches = tr.module_seconds(MODULE)
    if not launches or seconds <= 0:
        return None
    flops, nbytes = work.compress_work(rec["compress_leaf_sizes"])
    ideal = work.ideal_seconds(flops, nbytes, rec["peaks"])
    return 100.0 * ideal * launches / seconds
