"""inspect-12mp [image] — high-bit-depth machine-vision inspection.

A Sony IMX253 sensor (4096x3000, 12 bits a pixel, as in the Basler ace
acA4112-30um) delivers GenICam PFNC ``Mono12``: 12-bit values in 16-bit
containers, so each triggered capture reaches the host as a ``uint16``
frame. The kernels read it raw at 2 bytes a pixel and widen it per block
in VMEM; ``precision="auto"`` picks the lane from the frame's dtype. The
operator is the paper's four-directional 5x5 Sobel (RG-v2); the block
shape comes from the tuner (``sobel_block_*`` 0).

The smoke config makes small ragged 12-bit frames for CPU runs.
"""
from repro.configs.base import ModelConfig, register

FULL = ModelConfig(
    name="inspect-12mp", family="image",
    image_h=3000, image_w=4096, frame_bits=12,
    sobel_operator="sobel5", sobel_directions=4, sobel_variant="v2",
    sobel_backend="auto", sobel_block_h=0, sobel_block_w=0,
)

SMOKE = FULL.replace(name="inspect-12mp-smoke", image_h=61, image_w=203)

register("inspect-12mp", FULL, SMOKE)
