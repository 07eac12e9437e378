"""Index build rate: corpus rows over the host-clock seconds of
``ANNIndex.build`` to ``block_until_ready`` on the graph, less the seconds
JAX reports compiling or loading programs inside it."""


def read(run):
    return run["n"] / run["build_s"]
