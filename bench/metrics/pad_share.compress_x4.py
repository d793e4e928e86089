"""Sharded chunk grid: pad elements sent through the sharded kernels per
real chunk element (the program's ``shard_pad_rows`` / ``shard_rows``
counters) over the traced window's requests.  None where the program
counts no sharded element."""
from bench import stages


def read(ctx):
    st = stages.analyse(ctx)
    if st is None or not st.counts.get("shard_rows"):
        return None
    return st.counts.get("shard_pad_rows", 0) / st.counts["shard_rows"]
