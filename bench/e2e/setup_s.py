"""Set-up: seconds from the process's start to the window's start (imports,
data, the build, warm-up; in a side's first run, compilation)."""


def read(run):
    return run["setup_s"]
