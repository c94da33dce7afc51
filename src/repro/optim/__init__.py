from repro.optim import adamw, clip, schedules  # noqa: F401
