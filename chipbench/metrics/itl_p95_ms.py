"""95th percentile of the gaps between consecutive output tokens of a
request, over every request and gap in the window."""
import readers


def read(run):
    v = readers.percentile(readers.itl_s(run), 95)
    return None if v is None else 1e3 * v
