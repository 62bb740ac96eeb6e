"""A configuration's sizes are read by its model family
(``bench/families/<model_type>.py``, ``dims``). ``Dims`` is the
grouped-query family's, which Qwen2 and Mixtral share, under the name
the tools and tests have imported it by."""
from bench.families._gqa import Dims  # noqa: F401
