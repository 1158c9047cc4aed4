"""Whole-stage / agg layer: stage_compiled / stage_attempts over the window
(compile_service.TELEMETRY), in percent; nothing where no stage was tried."""


def read(run):
    attempts = run["telemetry"].get("stage_attempts")
    if not attempts:
        return None
    return 100.0 * run["telemetry"].get("stage_compiled", 0) / attempts
