"""Set-up: process start to window start (loading, compiling or loading
programs from the persistent cache, warming up)."""


def read(run):
    return run["setup_s"]
