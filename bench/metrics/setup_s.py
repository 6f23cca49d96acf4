"""Set-up seconds: process start to the first instant of the window
(device start, graphs, tables, compiles or compile-cache reads, warm-up)."""


def read(rec):
    return rec["setup_s"]
