"""Compile layer: programs compiled (compile_service.TELEMETRY compile_count)
between the end of warm-up and the end of the window; must read 0."""


def read(run):
    return run["telemetry"].get("compile_count")
