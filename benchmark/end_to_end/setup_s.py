"""Process start to the first timed operation: build check, daemons,
sidecar start and warm-up, making the data, preload."""


def read(cell: dict):
    return cell["setup_s"]
