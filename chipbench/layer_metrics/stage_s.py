# L3 ingest: seconds per fit inside the program's `stage` span (host rows ->
# RowStager -> device), from the fit reports of the window's fits.


def read(ctx):
    staged = [f["stage_s"] for f in ctx["fits"] if f["stage_s"] > 0]
    return sum(staged) / len(staged) if staged else None
