"""Compilations inside the window, which should read 0: the larger of
runtime/fuse.py's traces and JAX's own backend-compile events (a load from
the persistent cache fires one too). A run where it is not 0 says so."""


def read(ctx):
    fuse = ctx["after"]["fuse"]["traces"] - ctx["before"]["fuse"]["traces"]
    xla = (ctx["after"]["xla"]["backend_compiles"]
           - ctx["before"]["xla"]["backend_compiles"])
    return max(fuse, xla)
