"""Pages of KV the paged decode-attention kernel's live grid steps carry,
on average: ``paged_live_pages / paged_live_grid_steps`` of ServeEngine.stats,
summed over every drain of the run (a count the program makes on the host
from each decode chunk's lengths, so a CPU run reads it too). Engines that
do not run the paged kernel count nothing and read nothing."""
KIND, UNIT = "per_layer", "pages"


def read(records, red):
    if records.get("kind") != "serve":
        return None
    e = records["engine"]
    steps = e["paged_live_grid_steps"]
    return e["paged_live_pages"] / steps if steps else None
