"""What decides ``correct``: answers against the plain reference, and the
checks that the device -- not a fallback -- served them."""

import json
from collections import Counter


def multiset(rows) -> Counter:
    return Counter(tuple(r) for r in rows)


def rows_of(body: bytes):
    """The rows of a ``/store/query`` response, or None where it has none."""
    try:
        return json.loads(body)["data"]
    except (ValueError, KeyError, TypeError):
        return None


def wrong_answers(requests, reference_rows):
    """Indices of the requests whose answer is not the reference's multiset.

    ``requests``: ``[{"text", "status", "body"}]``; ``reference_rows(text)``
    gives the reference's rows.  Every send of every distinct text is
    compared; bodies equal byte for byte are decoded once.
    """
    want, seen, bad = {}, {}, []
    for i, r in enumerate(requests):
        if r["status"] != 200:
            bad.append(i)
            continue
        if r["text"] not in want:
            want[r["text"]] = multiset(reference_rows(r["text"]))
        key = (r["text"], r["body"])
        if key not in seen:
            rows = rows_of(r["body"])
            seen[key] = rows is not None and multiset(rows) == want[r["text"]]
        if not seen[key]:
            bad.append(i)
    return bad, want
