"""Consoles and apps that follow one vehicle of the fleet each: a churned
subscription opens a vehicle's live view for a few seconds and closes
it (a ``churn`` group's generator, README.md "A churn group").

Filter ``k`` follows vehicle ``k`` (ids 1 to ``filters``, the hottest of
the fleet pool's Zipf draw): odd ids ``vehicles/v<k>/+/temp``, even ids
``vehicles/v<k>/+/#``.  Each matches that vehicle's pool topic
(``vehicles/v<k>/sensors/temp``) and no other vehicle's, so no pool
topic matches two of them; and none is a string that the table or the
live set holds, so each is a new route the engine's residual carries
until a fold.

Standard library and numpy only (README.md, "A generator").
"""


def churn(rng, pops, filters):
    """``filters`` wildcard filters, one a vehicle id from 1 on; more
    than the table has vehicles (``pops[0]``) is refused."""
    n_vehicles = pops[0]
    if not 1 <= filters <= n_vehicles:
        raise ValueError(
            f"filters must be 1 to the table's {n_vehicles} vehicles: "
            f"{filters}"
        )
    return [
        f"vehicles/v{k}/+/temp" if k % 2 else f"vehicles/v{k}/+/#"
        for k in range(1, filters + 1)
    ]
