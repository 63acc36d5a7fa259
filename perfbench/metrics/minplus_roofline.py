"""The min-plus sketch kernel's share of its HBM roofline, in %: the least
time the chip's HBM needs for the bytes the sketch must move (the
unpadded ``(B, R)`` and ``(R, R)`` int32 operands in, ``(B, R)`` out),
at the peak bandwidth of ``peaks.json``, over the kernel's device time.
Only the bandwidth bound is used: no peak for the VPU's int32 min/add is
published, so none is assumed."""

KERNEL = r"minplus"


def read(obs):
    import roofline
    import tracereduce

    ev = tracereduce.op_events(obs.trace, KERNEL)
    t = sum(b - a for a, b in ev) / 1e9
    if not ev or t <= 0:
        return None
    need = len(ev) * roofline.minplus_bytes(obs.chunk, obs.n_landmarks)
    return 100.0 * need / roofline.peak(obs.device_kind)["hbm_bytes_per_s"] / t
