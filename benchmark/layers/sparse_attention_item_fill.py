"""``sparse_attention_item_fill``: listed pages per work item of the
grouped-heads attention kernel, ``serving_attention_item_pages_total`` over
``serving_attention_items_total`` from the program's default registry (the
step counts both on the device).  8 is a full item, 1 an item a page.  A
ratio of the process's totals, warm-up included: the same step on the same
traffic.  ``None`` for a program without the counters."""


def read(run):
    try:
        from paddle_tpu.observability.metrics import default_registry
    except ImportError:
        return None
    registry = default_registry()
    items = registry.get("serving_attention_items_total")
    pages = registry.get("serving_attention_item_pages_total")
    if items is None or pages is None or not items.value:
        return None
    return pages.value / items.value
