-- TPC-DS v3.2 query 67 (query67.tpl), the month sequence of its qualification run:
-- DMS = 1200, d_month_seq between 1200 and 1200 + 11 (the twelve months of 2000).
-- Departures from the spec's text, none of which changes an answer:
--   the comma joins are explicit JOIN ... ON (the parser has no comma join), the fact
--   table's key first; the WHERE keeps the one conjunct that is no join condition;
--   ss_sales_price, a decimal(7,2), is a double (as the TPC-H cells hold decimal(15,2)),
--   so sumsales is a double sum and not a decimal(38,2);
--   "limit 100" is the template's [_LIMITC] in its LIMIT form.
select *
from (select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id,
             sumsales,
             rank() over (partition by i_category order by sumsales desc) rk
      from (select i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy,
                   s_store_id,
                   sum(coalesce(ss_sales_price * ss_quantity, 0)) sumsales
            from store_sales
                join date_dim on ss_sold_date_sk = d_date_sk
                join store on ss_store_sk = s_store_sk
                join item on ss_item_sk = i_item_sk
            where d_month_seq between 1200 and 1200 + 11
            group by rollup(i_category, i_class, i_brand, i_product_name, d_year, d_qoy,
                            d_moy, s_store_id)) dw1) dw2
where rk <= 100
order by i_category, i_class, i_brand, i_product_name, d_year, d_qoy, d_moy, s_store_id,
         sumsales, rk
limit 100
