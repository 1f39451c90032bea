"""XLA compile requests that fell inside the measured window
(`jax.monitoring`, as `run.CompileLog` hears them): how many
(``requests``), how many the persistent cache did not have (``fresh``),
or their ``seconds``."""


def read(run, what="requests"):
    return run["compiles"].get(what)
