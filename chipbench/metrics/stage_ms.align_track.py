"""Host milliseconds per replay window spent in the pipeline's
``AlignTrackStage`` (the program's own ``stage_wall_s`` self time), over
every job of the window."""
import bench


def read(ctx):
    return bench.stage_ms(ctx, "AlignTrackStage")
