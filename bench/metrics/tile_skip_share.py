"""Share of tiles the stream engine's delta test skipped in the window:
``skipped_tiles`` over ``tiles_per_frame`` times frames served, summed over
streams, in %."""


def read(ctx):
    rec = ctx["record"]
    total = rec.get("tiles_per_frame", 0) * rec.get("frames_counted", 0)
    return 100.0 * rec["skipped_tiles"] / total if total else None
