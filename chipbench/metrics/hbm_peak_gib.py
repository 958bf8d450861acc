"""Peak device memory in GiB after the window, on the fullest chip: the TPU
runtime's ``peak_bytes_in_use`` (buffers) plus ``peak_bytes_reserved`` (the
scratch memory compiled programs reserve), which the first counter leaves
out."""


def read(rec):
    peak = rec.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
