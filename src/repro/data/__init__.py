from repro.data.microbiome import (synthetic_abundance,  # noqa: F401
                                   synthetic_sparse_counts, synthetic_study)
from repro.data.slabcache import (SlabCache, SlabCacheError,  # noqa: F401
                                  SlabCacheWriter, SlabPrefetcher,
                                  build_slab_cache)
