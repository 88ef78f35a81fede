"""DeepSpeed-TPU: a TPU-native large-scale training & inference framework.

Public API parity with the reference ``deepspeed/__init__.py``:
``initialize()`` (:69), ``init_distributed`` (re-export), ``init_inference``
(:273), ``add_config_arguments`` (:250) — implemented over JAX/XLA/Pallas.
"""

from . import comm  # noqa: F401
from .accelerator import get_accelerator  # noqa: F401
from .runtime.config import DeepSpeedConfig
from .runtime.engine import DeepSpeedEngine
from .utils.logging import log_dist, logger  # noqa: F401

__version__ = "0.1.0"
__git_hash__ = None
__git_branch__ = None


def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    distributed_port: int = 29500,
    mpu=None,
    dist_init_required: bool = None,
    collate_fn=None,
    config=None,
    mesh_config=None,
    config_params=None,
):
    """Build a training engine (reference ``deepspeed/__init__.py:69``).

    Returns the 4-tuple ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    ``model`` is a (params, apply_fn) pair or an object exposing
    ``.params``/``.apply`` (see ``DeepSpeedEngine._extract_model``); ``mpu`` is
    accepted for signature parity — mesh axes replace the mpu contract, configured
    via the ``mesh`` config block.
    """
    log_dist(f"DeepSpeed-TPU info: version={__version__}", ranks=[0])
    assert model is not None, "deepspeed_tpu.initialize: model is a required argument"

    if config is None:
        config = config_params
    if config is None and args is not None and getattr(args, "deepspeed_config", None):
        config = args.deepspeed_config
    assert config is not None, (
        "DeepSpeed requires --deepspeed_config to specify configuration file, or a "
        "config= dict/path argument"
    )

    # config drives the mesh; build it before the engine
    import jax

    ds_config = DeepSpeedConfig(config, mesh_shape=mesh_config, world_size=jax.device_count())
    if mpu is not None:
        logger.warning(
            "mpu argument is accepted for parity but ignored: tensor parallelism is "
            "configured via the 'mesh' config block on TPU"
        )
    # ZeRO++ hpZ / MiCS secondary partition becomes the `hpz` mesh axis
    zc = ds_config.zero_config
    mics = zc.mics_shard_size if zc.mics_shard_size and zc.mics_shard_size > 0 else 1
    if (zc.zero_hpz_partition_size > 1 and mics > 1
            and zc.zero_hpz_partition_size != mics):
        raise ValueError(
            f"zero_hpz_partition_size={zc.zero_hpz_partition_size} conflicts "
            f"with mics_shard_size={mics}")
    hpz = max(zc.zero_hpz_partition_size, mics)
    if hpz > 1 and zc.stage < 3:
        logger.warning(
            f"zero_hpz_partition_size/mics_shard_size={hpz} only applies at ZeRO "
            f"stage 3 (got stage {zc.stage}); ignoring — parity with reference")
        hpz = 1
    mc = ds_config.mesh_config
    if hpz > 1 and mc.hpz != 1 and mc.hpz != hpz:
        raise ValueError(
            f"mesh.hpz={mc.hpz} conflicts with zero_hpz_partition_size/"
            f"mics_shard_size={hpz}")
    if hpz > 1 and mc.hpz == 1:
        if mc.data:
            if mc.data % hpz:
                raise ValueError(
                    f"zero_hpz_partition_size/mics_shard_size {hpz} does not "
                    f"divide mesh.data {mc.data}")
            mc.data //= hpz
        mc.hpz = hpz

    comm.init_distributed(mesh_config=ds_config.mesh_config)
    comm.configure(config=ds_config)

    # engine dispatch (reference __init__.py:166-206: pipeline models get the
    # PipelineEngine; stage-3 offload_param gets the layer-streamed
    # ZeRO-Infinity engine)
    from .runtime.pipe.engine import PipelineEngine
    from .runtime.pipe.module import PipelinedLM, PipelineModule

    off_p = zc.offload_param
    if off_p is not None and off_p.device in ("cpu", "nvme"):
        if zc.stage < 3:
            raise ValueError(
                "zero_optimization.offload_param requires stage 3 "
                "(parity with reference offload_param)")
        unsupported = {"optimizer": optimizer, "training_data": training_data,
                       "collate_fn": collate_fn,
                       "model_parameters": model_parameters}
        given = [k for k, v in unsupported.items() if v is not None]
        if given:
            raise ValueError(
                f"offload_param (layer-streamed) engine does not support the "
                f"{given} argument(s): the optimizer is the host CPUAdam from "
                "the config's optimizer block, and data is passed to "
                "train_batch(data_iter) directly")
        from .runtime.swap_tensor import StreamedZeroEngine

        engine = StreamedZeroEngine(model, ds_config, lr_scheduler=lr_scheduler)
        return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler

    engine_cls = (
        PipelineEngine if isinstance(model, (PipelinedLM, PipelineModule)) else DeepSpeedEngine
    )
    engine = engine_cls(
        model=model,
        config=ds_config,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        training_data=training_data,
        collate_fn=collate_fn,
        model_params=model_parameters,
    )
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def add_config_arguments(parser):
    """Add --deepspeed flags to an argparse parser (reference ``:250``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to easily toggle)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated alias of --deepspeed")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated alias of --deepspeed_config")
    return parser


def init_distributed(*args, **kwargs):
    return comm.init_distributed(*args, **kwargs)


def init_inference(model, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed/__init__.py:273``)."""
    from .inference.engine import init_inference as _init_inference

    return _init_inference(model, config=config, **kwargs)
