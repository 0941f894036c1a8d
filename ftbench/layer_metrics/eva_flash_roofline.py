"""Kernels: the chunk-summary attention kernels' share of their roofline.  The
least time for the attention of one step (``eva_flops.flash_step``: forward
and backward over the LIVE pairs alone, a window's triangle on tokens and the
earlier windows' summaries, never the blocks walked; q, k, v, o and their
gradients credited once) over the device time of ``eva_fwd``, ``eva_dq`` and
``eva_dkv``.  A kernel that masked a full causal walk would read an eighth of
what one that skips reads."""

META = dict(source="device_trace", layer="kernels", unit="%", moves="tokens_per_s_per_chip")


def read(sources):
    from ftbench import flops as peaks
    from ftbench.layer_metrics import _eva

    if not _eva.is_mine(sources):
        return None
    seconds = _eva.kernel_s_per_step(sources, _eva.EVA)
    if seconds is None:
        return None
    need = _eva.flops().flash_step(sources["shapes"], sources["rows_per_replica"], sources["seq"])
    return peaks.roofline_pct(*need, seconds, sources["device_kind"])["pct"]
