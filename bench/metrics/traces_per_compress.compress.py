"""Encode sweep: jaxprs JAX traced per compress (the program's
``traces`` counter, summed over the traced window's requests)."""
from bench import stages


def read(ctx):
    st = stages.analyse(ctx)
    if st is None or not st.requests:
        return None
    return st.counts.get("traces", 0) / st.requests
