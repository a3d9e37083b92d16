from .search import (
    SearchGeometry,
    bank_params_host,
    init_state,
    make_bank_step,
    run_bank,
    template_params_host,
    template_sumspec_fn,
    upload_bank,
)

__all__ = [
    "SearchGeometry",
    "bank_params_host",
    "init_state",
    "make_bank_step",
    "run_bank",
    "template_params_host",
    "template_sumspec_fn",
    "upload_bank",
]
