# L3 ingest: the rows' bytes (rows x cols x 4, padding not counted) over the
# staging seconds per fit.
from chipbench import manifest


def read(ctx):
    stage_s = manifest.reader("stage_s")(ctx)
    return ctx["rows"] * ctx["cols"] * 4 / 1e9 / stage_s if stage_s else None
