"""Peak bytes in use on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), GiB — the program's, read
before the reference runs."""


def read(run):
    return run["memory_peak_bytes"] / 2**30 or None
