"""All acknowledged upload bytes over the whole window (first send to the
last acknowledgement), from the clients' side, in MB (1e6 bytes) per s."""


def read(cell: dict):
    done = sum(up["bytes"] for up in cell["uploads"])
    return done / 1e6 / cell["window_s"] if done else None
