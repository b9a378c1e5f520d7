"""Programs compiled or fetched from the persistent cache inside the
measured window; the warm job should have left none."""


def read(run: dict):
    return run["compiles_in_window"]
