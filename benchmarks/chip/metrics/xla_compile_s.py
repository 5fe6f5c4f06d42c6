"""Seconds the program itself records for the XLA compile of the cell's
executables (``compile_us`` of its AOT or batch entries); a compile that
the persistent cache serves reads low."""


def read(ctx):
    return ctx["setup"]["compile_s"]
