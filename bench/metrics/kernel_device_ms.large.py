"""Device ms per solve in the Pallas kernels (ops of kind
``tpu_custom_call``), from the profiler trace of the window."""


def read(run):
    if run.trace is None or "kernels" not in run.trace["layers"]:
        return None
    seconds = run.trace["layers"]["kernels"]["seconds"]
    return seconds * 1e3 / run.window.problems
