"""Data pipeline.

Reference: ``src/io/`` iterators + ``python/mxnet/io/io.py`` (SURVEY.md §2.4).
The contract that matters for elasticity is the reference's sharding pair
``num_parts``/``part_index`` (``src/io/image_iter_common.h:127-162``) and the
``ResizeIter`` equal-batches-per-worker semantics (``fit.py:38-43``) — both
preserved here.  ``ElasticDataIterator`` is the ``BaseDataIterator`` contract
(``python/mxnet/module/base_data_iterator.py``): a factory the fit loop calls
after a membership change to re-shard.
"""

from dt_tpu.data.io import (
    DataBatch as DataBatch,
    DataDesc as DataDesc,
    DataIter as DataIter,
    NDArrayIter as NDArrayIter,
    CSVIter as CSVIter,
    LibSVMIter as LibSVMIter,
    ResizeIter as ResizeIter,
    PrefetchingIter as PrefetchingIter,
    DevicePrefetchIter as DevicePrefetchIter,
    SyntheticImageIter as SyntheticImageIter,
    ElasticDataIterator as ElasticDataIterator,
)
from dt_tpu.data import augment as augment
from dt_tpu.data.mnist import MNISTIter as MNISTIter
from dt_tpu.data.dataset import (
    Dataset as Dataset,
    ArrayDataset as ArrayDataset,
    DataLoader as DataLoader,
    RandomSampler as RandomSampler,
    SequentialSampler as SequentialSampler,
)
from dt_tpu.data.bucket_io import BucketSentenceIter as BucketSentenceIter
from dt_tpu.data.block_diffusion import (
    BlockDiffusionIter as BlockDiffusionIter,
    block_diffusion_noise as block_diffusion_noise,
)
from dt_tpu.data.recordio import (
    RecordIOReader as RecordIOReader,
    RecordIOWriter as RecordIOWriter,
    pack_label as pack_label,
    unpack_label as unpack_label,
    ImageDetRecordIter as ImageDetRecordIter,
    ImageRecordIter as ImageRecordIter,
)
