from .ops import interp_recon, interp_recon_batch, interp_recon_sharded
from .ref import interp_recon_ref

__all__ = ["interp_recon", "interp_recon_batch", "interp_recon_sharded",
           "interp_recon_ref"]
