set batch_rows = 4096;
set ivf_nprobe = 16;
set query_shards = 0;
select 1;
