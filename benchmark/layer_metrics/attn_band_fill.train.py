"""attn_band_fill.train: of the score-tile area the flash kernels' grids walk in a step (iotml_flash_mask_walked_area: tiles x block_q x block_k a head, by kernel and mask, times the layers under that mask), the share inside the mask (iotml_flash_mask_live_area); the rest is the masked part of the band's and the triangle's edge tiles."""

from benchmark import harness as hs

AREA = 'iotml_flash_mask_%s_area{kernel="%s",kind="%s"}'
LAYERS = 'iotml_model_layers{kind="%s"}'
#: the mixer kind whose flash calls run under each mask
MASKS = {"causal": "attention", "band": "window_attention"}


def read(run):
    said = hs.registry()
    walked = live = 0.0
    for mask, kind in MASKS.items():
        layers = said.get(LAYERS % kind, 0)
        for kernel in ("fwd", "bwd_dkv", "bwd_dq"):
            walked += layers * said.get(AREA % ("walked", kernel, mask), 0)
            live += layers * said.get(AREA % ("live", kernel, mask), 0)
    # nothing to read: a program without the gauges (the parent's), a
    # configuration without a window layer, a rehearsal (the plain
    # attention walks no tiles)
    if not walked or not said.get(AREA % ("walked", "fwd", "band")) \
            or "sliding_window_layout" not in run.cfg:
        return None
    return 100.0 * live / walked
