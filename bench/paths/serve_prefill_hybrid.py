"""Prefill of a hybrid model (Mamba2 layers and shared attention blocks):
``serve_prefill``'s window and comparison, with the kernels the hybrid's
step calls. With ``use_flash`` the step runs the flash kernel once per
application of a shared block (not once per layer), at the block's head
width, and the ``ssd_scan`` kernel once per layer for the chunked SSD's
intra-chunk term."""

from __future__ import annotations

from bench.paths import serve_prefill
from bench.work import ssd_scan

METRIC = serve_prefill.METRIC
#: The instruction base of ``ssd_scan``'s events: the jitted wrapper of
#: its Pallas call (``kernels/ssd_scan/kernel.py``).
SSD_FAMILY = "ssd_intra_chunk_bh"


class Path(serve_prefill.Path):
    def flash_calls(self) -> int:
        if not (self.use_flash and self.prompt >= 512):
            return 0
        return len(self.cfg.hybrid_layer_ids)

    def work(self) -> dict:
        out = super().work()
        if self.use_flash:
            c = self.cfg
            one = ssd_scan.work(self.batch, self.prompt,
                                c.ssm_expand * c.d_model // c.ssm_head_dim,
                                c.ssm_groups, c.ssm_state, c.ssm_head_dim,
                                256)
            out["kernels"][SSD_FAMILY] = [one] * c.n_layers
        return out


def setup(ctx):
    return Path(ctx)
