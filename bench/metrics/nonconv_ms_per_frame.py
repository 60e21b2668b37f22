"""nonconv_ms_per_frame.<mix>: device time a frame outside the conv
fusions (``bench/conv_ops.json``) in the profiled window, in milliseconds:
device busy time less conv time, over the frames answered there.

It is not the time of the non-conv node kinds alone.  Beside the
activation passes, concats, pools, upsamples and decode it holds the ops
that run in a conv node's scope but outside its fusion: the quantisation
of the conv's input to int8 and the copies and pads that lay it out."""


def read(run):
    t = run.trace
    if not t or not t["fetches"]:
        return None
    return 1e3 * (t["busy_s"] - t["conv_s"]) / (t["fetches"] * run.cell.batch)
