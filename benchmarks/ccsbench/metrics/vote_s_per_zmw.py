"""vote_s_per_zmw: device self-seconds of the ``vote`` stage per
record of the window: the stage's seconds over the traced quarter
slices (stages.py), over those slices' traced seconds, times the
window's seconds, over its records: the slices and scaling dp_gcups
uses.  Nothing to read (None) where the trace carries no stages."""

import stages


def read(ctx):
    return stages.s_per_zmw(ctx, "vote")
