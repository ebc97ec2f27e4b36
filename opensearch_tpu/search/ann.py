"""Live-tunable ANN serving configuration (the ANNS-AMP knobs + the
kernel selection policy).

The kNN serving paths (executor.shard_knn_selection's ANN and exact
branches) read five dynamic settings on every dispatch:

  search.knn.ann.adc_precision       "fp32" | "bf16" | "int8"
  search.knn.ann.rescore_multiplier  exact-rescore pool = multiplier * k
  search.knn.ann.kernel              "auto" | "pallas" | "xla"
  search.knn.kernel                  "auto" | "pallas" | "xla" (EXACT path)
  search.knn.score_precision         "fp32" | "bf16" | "int8" (EXACT scan)

``search.knn.kernel`` extends the ANN policy's auto/pallas/xla shape to
the EXACT path (ISSUE 19): which lowering of the one exact scan
(ops/pallas_knn.knn_fused) a launch runs — the blockwise kernel (running
top-R pool in VMEM, only [B, R] winners to HBM) or its XLA twin — is
decided by ONE rule, ops/pallas_knn.fused_impl, from this policy, the
platform and the launch's k; ``search.knn.score_precision`` picks the
SCAN's matmul width (reduced precisions widen the pool and exact-rescore
in fp32, so returned scores stay in the serving score space). What the
rule resolved and the precision ride the batch key, so a live flip never
re-ranks an in-flight batch.

Reduced-precision ADC (ops/ivfpq.search) only ranks CANDIDATES; the fused
program always ends in an exact fp32 rescore over the widened pool, so
recall recovers while the ADC scan sheds bytes (ANNS-AMP, PAPERS.md). All
three values ride the batch key: flipping a knob mid-stream starts new
batches under the new configuration and can never re-rank (or re-route)
an in-flight one.

``kernel`` selects the ADC scan implementation (:func:`resolve_kernel`):
"xla" is the monolithic ops/ivfpq.search lowering; "pallas" is the fused
blockwise scan (ops/pallas_adc) behind the FusionANNS-style host/device
cooperative split — host coarse quantization + probe selection, one
batched device scan — interpreted only when the backend is the CPU (the
tests' parity path; NOT a speed path there). "auto"
resolves to "pallas" on a TPU backend and "xla" elsewhere, so the CPU sim
keeps the fast lowering unless a test/soak forces the kernel.

The config object is PROCESS-wide for the same reason the kNN dispatch
batcher is (search/batcher.py `default_batcher`): the executor's dispatch
sites are module-level code with no node handle, and one process serves
one device. TpuNode / ClusterNode apply dynamic settings into it with the
same guarded adapter shape as the batch settings, so a sibling in-process
node's unrelated update can never clobber live configuration.

``bucket_nprobe`` is the serving tier's nprobe shape policy: nprobe is a
static jit argument, so raw per-request values would compile one fused
program per distinct nprobe. Bucketing to the next power of two (clamped
to nlist) keeps the program cache warm; extra probes only ever ADD recall.
"""

from __future__ import annotations

from opensearch_tpu.common.settings import Property, Setting


def _validate_precision(v: str) -> None:
    # single source of truth for the precision set is the kernel module
    # (ops/ivfpq.ADC_PRECISIONS — the dtypes the fused search compiles
    # for); imported lazily so settings registration stays jax-free
    from opensearch_tpu.ops.ivfpq import ADC_PRECISIONS

    if v not in ADC_PRECISIONS:
        raise ValueError(
            f"unknown [search.knn.ann.adc_precision] value [{v}] "
            f"(choose from {list(ADC_PRECISIONS)})"
        )


# ADC kernel selection policies the serving tier accepts ("auto" resolves
# per platform at dispatch time; see resolve_kernel)
ANN_KERNELS = ("auto", "pallas", "xla")


def _validate_kernel(v: str) -> None:
    if v not in ANN_KERNELS:
        raise ValueError(
            f"unknown [search.knn.ann.kernel] value [{v}] "
            f"(choose from {list(ANN_KERNELS)})"
        )


ADC_PRECISION_SETTING: Setting[str] = Setting(
    "search.knn.ann.adc_precision", "fp32", str,
    Property.NODE_SCOPE, Property.DYNAMIC,
    validator=_validate_precision,
)
RESCORE_MULTIPLIER_SETTING = Setting.int_setting(
    "search.knn.ann.rescore_multiplier", 4,
    Property.NODE_SCOPE, Property.DYNAMIC, min_value=1, max_value=256,
)
KERNEL_SETTING: Setting[str] = Setting(
    "search.knn.ann.kernel", "auto", str,
    Property.NODE_SCOPE, Property.DYNAMIC,
    validator=_validate_kernel,
)


def _validate_exact_kernel(v: str) -> None:
    if v not in ANN_KERNELS:
        raise ValueError(
            f"unknown [search.knn.kernel] value [{v}] "
            f"(choose from {list(ANN_KERNELS)})"
        )


def _validate_score_precision(v: str) -> None:
    # single source of truth is the fused exact kernel module
    # (ops/pallas_knn.SCORE_PRECISIONS); lazy import keeps settings
    # registration jax-free
    from opensearch_tpu.ops.pallas_knn import SCORE_PRECISIONS

    if v not in SCORE_PRECISIONS:
        raise ValueError(
            f"unknown [search.knn.score_precision] value [{v}] "
            f"(choose from {list(SCORE_PRECISIONS)})"
        )


# the EXACT path's kernel policy (ISSUE 19): same auto/pallas/xla shape as
# the ANN policy, read by ops/pallas_knn.fused_impl to pick the lowering of
# the one exact scan (ops/pallas_knn.knn_fused)
EXACT_KERNEL_SETTING: Setting[str] = Setting(
    "search.knn.kernel", "auto", str,
    Property.NODE_SCOPE, Property.DYNAMIC,
    validator=_validate_exact_kernel,
)
SCORE_PRECISION_SETTING: Setting[str] = Setting(
    "search.knn.score_precision", "fp32", str,
    Property.NODE_SCOPE, Property.DYNAMIC,
    validator=_validate_score_precision,
)

ANN_SETTINGS = (ADC_PRECISION_SETTING, RESCORE_MULTIPLIER_SETTING,
                KERNEL_SETTING, EXACT_KERNEL_SETTING,
                SCORE_PRECISION_SETTING)


def resolve_kernel(policy: str) -> str:
    """The EFFECTIVE ADC scan for this dispatch: "pallas" or "xla". The
    resolved value (not the policy) rides the batch key — two nodes of one
    process can never disagree about what a merged batch will launch, and
    a policy flip mid-stream starts new batches instead of re-routing an
    in-flight one. "auto" keeps the XLA lowering off-TPU because
    interpret-mode Pallas is a parity tool, not a serving speed path."""
    if policy in ("pallas", "xla"):
        return policy
    import jax

    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"


def bucket_nprobe(nprobe: int, nlist: int) -> int:
    """Power-of-two ceiling, clamped to [1, nlist] (nprobe is a static
    shape arg of the fused search; more probes never lose recall)."""
    nprobe = max(1, int(nprobe))
    return min(1 << (nprobe - 1).bit_length(), max(1, int(nlist)))


class AnnServingConfig:
    """Process-wide ANN serving knobs, applied live by the settings tier.

    Fields are plain atomic assignments read racily by design (the
    dynamic-settings contract, same as KnnDispatchBatcher.configure): a
    dispatch that read the old values completes under the old policy — and
    since both values are part of the batch key, never inside a batch
    formed under the new one.
    """

    def __init__(self) -> None:
        from opensearch_tpu.common.settings import Settings

        self.adc_precision: str = ADC_PRECISION_SETTING.default(
            Settings.EMPTY)
        self.rescore_multiplier: int = RESCORE_MULTIPLIER_SETTING.default(
            Settings.EMPTY)
        self.kernel: str = KERNEL_SETTING.default(Settings.EMPTY)
        self.exact_kernel: str = EXACT_KERNEL_SETTING.default(
            Settings.EMPTY)
        self.score_precision: str = SCORE_PRECISION_SETTING.default(
            Settings.EMPTY)

    def configure(self, *, adc_precision: str | None = None,
                  rescore_multiplier: int | None = None,
                  kernel: str | None = None,
                  exact_kernel: str | None = None,
                  score_precision: str | None = None) -> None:
        if adc_precision is not None:
            _validate_precision(adc_precision)
            self.adc_precision = adc_precision
        if rescore_multiplier is not None:
            self.rescore_multiplier = max(1, int(rescore_multiplier))
        if kernel is not None:
            _validate_kernel(kernel)
            self.kernel = kernel
        if exact_kernel is not None:
            _validate_exact_kernel(exact_kernel)
            self.exact_kernel = exact_kernel
        if score_precision is not None:
            _validate_score_precision(score_precision)
            self.score_precision = score_precision

    def apply_settings(self, flat: dict) -> None:
        """Pick this config's keys out of a flat effective-settings map
        (the cluster-settings update consumer; absent keys -> defaults)."""
        from opensearch_tpu.common.settings import Settings

        s = Settings.from_flat({
            st.key: flat[st.key] for st in ANN_SETTINGS if st.key in flat
        })
        self.configure(
            adc_precision=ADC_PRECISION_SETTING.get(s),
            rescore_multiplier=RESCORE_MULTIPLIER_SETTING.get(s),
            kernel=KERNEL_SETTING.get(s),
            exact_kernel=EXACT_KERNEL_SETTING.get(s),
            score_precision=SCORE_PRECISION_SETTING.get(s),
        )

    def snapshot(self) -> dict:
        from opensearch_tpu.ops.pallas_knn import fused_impl

        out = {
            "adc_precision": self.adc_precision,
            "rescore_multiplier": self.rescore_multiplier,
            "kernel": self.kernel,
            "exact_kernel": self.exact_kernel,
            "score_precision": self.score_precision,
            # what the policies mean on THIS backend: the kernels the next
            # dispatch launches (and keys its batch by; the exact one for a
            # k within the kernel's cap)
            "resolved": {
                "kernel": resolve_kernel(self.kernel),
                "exact_kernel": fused_impl(self.exact_kernel, 1)[0],
            },
        }
        # index-build accounting (index/device.py): how many IVF-PQ
        # structures this process built at publish time, and their cost
        from opensearch_tpu.index.device import ann_build_stats

        out["index_builds"] = ann_build_stats()
        return out


default_config = AnnServingConfig()
