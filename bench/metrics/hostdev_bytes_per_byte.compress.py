"""Encode sweep: bytes moved between host and device (the program's
``h2d_bytes`` + ``d2h_bytes`` counters) per field byte compressed, over
the traced window's requests."""
from bench import stages


def read(ctx):
    st = stages.analyse(ctx)
    if st is None or not st.counts.get("field_bytes"):
        return None
    c = st.counts
    return (c.get("h2d_bytes", 0) + c.get("d2h_bytes", 0)) / c["field_bytes"]
