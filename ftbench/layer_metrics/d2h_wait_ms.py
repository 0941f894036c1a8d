"""Device-host boundary: spans ``tpuft/ddp/d2h`` (one a bucket) on replica
0's train thread: blocked on the leaves' copies to the host (the first
bucket also on the gradient program).  Since PR 44 no whole-leaf
``np.asarray`` lies inside it: a landed shard is written straight into its
bucket.  In the four-chip cell both chips' shards of each leaf of group 0.
Summed over a step's buckets, mean over the traced steps."""

META = dict(source="program_span", layer="device-host boundary", unit="ms", moves="ddp_tokens_per_s_per_chip")


def read(sources):
    from ftbench import program_spans

    return program_spans.per_step_ms(sources, "tpuft/ddp/d2h")
