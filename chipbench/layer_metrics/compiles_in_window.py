# compile: the program's `compiles_total` counter over the window.  The
# warm-up fits compile or load every program, so this should read 0.


def read(ctx):
    return ctx["compiles_in_window"]
