"""Tiny sizes at which the CPU can drive a whole run of each cell."""
IMAGE = {"fields": {"image_h": 64, "image_w": 128, "sobel_block_h": 0,
                    "sobel_block_w": 0}}
# 8x8 delta tiles of 16x64 pixels, so that a moving disk leaves tiles to
# skip; two cameras, three warm ticks.
STREAM = {"fields": {"image_h": 128, "image_w": 512, "sobel_block_h": 16,
                     "sobel_block_w": 64},
          "traffic": {"streams": 2, "warm_ticks": 3}}
TINY = {"hd-batch-mag": IMAGE, "cam1080-moving": STREAM,
        "cam1080-noisy": STREAM}


def tiny(workload, backend="xla"):
    """The cell at a tiny size on ``backend``. The Pallas interpreter takes
    longer than a 30 fps frame period for a step, and the stream engine
    sheds frames that keep missing it, so there the cameras run at 2 fps."""
    ov = TINY[workload]
    traffic = dict(ov.get("traffic", {}))
    if backend == "pallas-interpret" and traffic:
        traffic["fps"] = 2
    return {"fields": {**ov["fields"], "sobel_backend": backend},
            "traffic": traffic}
