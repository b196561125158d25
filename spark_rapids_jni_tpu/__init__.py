"""spark_rapids_jni_tpu — TPU-native Spark acceleration kernel framework.

A from-scratch, TPU-first framework with the capability surface of the
spark-rapids-jni native acceleration layer (reference at /root/reference).
Subpackage map (see each module's docstring for its reference citation):

- ``table``: Arrow-style columnar containers as JAX pytrees (the cudf
  ``table_view``/``column`` analogue, reference
  ``src/main/cpp/src/row_conversion.cu`` L1 foundation).
- ``ops.row_conversion``: JCUDF row-format <-> column conversion, the flagship
  kernel set (reference ``src/main/cpp/src/row_conversion.cu``).
- ``ops.hashing``: Spark-compatible murmur3 / xxhash64 (north-star kernels).
- ``parquet``: host-side native Parquet footer parse/prune/re-serialize
  (reference ``src/main/cpp/src/NativeParquetJni.cpp``).
- ``parallel``: sharded tables + ICI all-to-all shuffle over a device mesh
  (the capability the Spark plugin layers above the reference; new here).
- ``models``: columnar query pipeline operators (Project/Filter/HashAggregate/
  HashJoin) — the north-star workload drivers.
- ``utils.datagen``: profile-driven random table generator (reference
  ``src/main/cpp/benchmarks/common/generate_input.hpp``).
- ``faultinj``: fault injection at the runtime-API boundary (reference
  ``src/main/cpp/faultinj/faultinj.cu``).
- ``obs``: structured observability — timed spans over the operator entry
  points (wall + fenced device time, rows/bytes, per-span XLA compile
  counts, failure capture), a JSONL event sink (``SRJ_TPU_EVENTS=<path>``),
  and the ``python -m spark_rapids_jni_tpu.obs`` report CLI; the NVTX-range
  layer it subsumes lives in ``utils.tracing``.
- ``memory``: the RMM analogue — pooled host staging arena (native
  freelist, ``native/src/host_arena.cpp``) + PJRT device-buffer
  statistics/lifetime adaptor (reference RMM knobs,
  ``src/main/cpp/CMakeLists.txt:62-69``).
"""

from spark_rapids_jni_tpu.table import (  # noqa: F401
    DType,
    Column,
    Table,
    INT8, INT16, INT32, INT64,
    UINT8, UINT16, UINT32, UINT64,
    FLOAT32, FLOAT64, BOOL8, STRING,
    decimal32, decimal64, list_, struct_,
    attach_string_tail, string_tail,
)

__version__ = "0.1.0"
