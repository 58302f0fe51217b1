"""``latent_selection_page_fill``: how full the pages are that the full
layers' selected rows lie in, per cent — rows the latent kernel fetched for
the indexer's choice (``serving_latent_rows_total{kind="selected"}``) over
the pages they lay in (``serving_latent_row_pages_total``, a page counted
once a token and layer) times the page size.  100 is a choice of whole
pages; the lower, the more scattered it is, and the more fetching rows by
id saves over fetching the pages they lie in."""


def read(run):
    c = run["counts"]
    pages = c.get("latent_row_pages")
    if not pages or "latent_rows_selected" not in c:
        return None
    return 100.0 * c["latent_rows_selected"] / (pages * c["page_size"])
